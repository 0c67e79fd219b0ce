"""The trace reduction, on a trace recorded on a TPU v5e: three rounds
of the feasibility kernel, the aggregate sweep and a bf16 matmul step,
each inside a ``bench.<name>`` host span (``tiny_v5e.xplane.pb``)."""
from pathlib import Path

import pytest

from harness import trace

FIXTURE = Path(__file__).resolve().parent / "testdata" / "tiny_v5e.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_xplane(str(FIXTURE))


def test_modules_by_stable_name(reduced):
    assert reduced.module_calls("jit__feasible_pallas") == pytest.approx(
        [6.221e-6, 6.1975e-6, 6.26625e-6], rel=1e-3)
    assert len(reduced.module_calls("jit_sweep")) == 3
    assert len(reduced.module_calls("jit_matmul_step")) == 3
    totals = reduced.module_totals()
    assert totals["jit_sweep"][0] == 3
    assert totals["jit_sweep"][1] == pytest.approx(145.259e-6, rel=1e-3)


def test_busy_is_union_of_op_intervals(reduced):
    dev = reduced.devices[0]
    assert len(dev.ops) == 123
    # no bench.window span in this trace: the window is the ops' extent
    assert reduced.window == (46335412.0, 85241883.0)
    assert reduced.busy_s() == pytest.approx(218.599e-6, rel=1e-6)
    assert reduced.busy_s() <= sum(e - s for s, e in dev.ops) * 1e-9
    assert reduced.idle_share() == pytest.approx(
        1 - 218.599e-6 / 0.038906471, rel=1e-6)


def test_host_spans_on_the_profiler_clock(reduced):
    assert sorted(reduced.spans) == ["bench.mm", "bench.scan", "bench.sweep"]
    assert all(len(v) == 3 for v in reduced.spans.values())
    scan = reduced.spans["bench.scan"][0]
    first_kernel = reduced.devices[0].modules["jit__feasible_pallas"][0][0]
    assert scan[0] < first_kernel < scan[1]


def test_breakdown(reduced):
    ops = reduced.top_ops(3)
    assert [name for name, _ in ops][0] == "jit_sweep:fusion.6 s32[2497,4]"
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    gaps = dict(reduced.idle_gaps(10))
    idle = reduced.window_s - reduced.busy_s()
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-9)
    assert set(gaps) <= {"host", "bench.scan", "bench.sweep", "bench.mm"}


def test_interval_arithmetic():
    ivs = [(0, 2), (1, 3), (5, 6)]
    assert trace.merge(ivs) == [(0, 3), (5, 6)]
    assert trace.union_ns(ivs) == 4
    assert trace.clip(ivs, (2, 5.5)) == [(2, 3), (5, 5.5)]
    assert trace.complement([(1, 2), (4, 5)], (0, 6)) == \
        [(0, 1), (2, 4), (5, 6)]
    assert trace.op_label("%fusion.12 = bf16[32,8]{1,0:T(8,128)} f(x)") == \
        "fusion.12 bf16[32,8]"
    assert trace.module_name("jit_serve_step(123)") == "jit_serve_step"
