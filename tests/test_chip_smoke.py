"""``chip_smoke.py``'s phases at toy size on the CPU.

The phases assert their own results; on the CPU the scheduling plane
stays on numpy, so only the parity probes reach the device halves (the
Pallas kernel interpreted, the jitted sweep).  The platform check lives
in ``main()``, which must refuse to report success here.
"""
import dataclasses
import importlib.util
from pathlib import Path

import pytest

from repro.configs.registry import get_config


@pytest.fixture(scope="module")
def smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_scheduling_phase_toy(smoke):
    res = smoke.scheduling_phase(nodes=16, cores_per_socket=4, backlog=24,
                                 arrivals=8, seed=3)
    assert res["jobs"] == 32 and res["n_vertices"] == 177
    assert res["probes"] >= 2 and res["sweep_calls"] >= res["probes"]
    # the CPU probes run the interpreted kernel, never the compiled one
    assert res["kernel_compiled"] == 0
    assert res["kernel_interpret"] >= res["probes"]
    assert res["platforms"] == {"cpu"}
    assert res["max_pending"] > 1


def test_serving_phase_toy(smoke):
    res = smoke.serving_phase(smoke=True, prompt_len=16, gen=4)
    assert res["dtype"] == "float32" and res["n_layers"] == 2
    assert res["max_abs_err"] <= 1e-2 * res["max_abs_ref"]
    assert res["argmax_agree"] == 1.0


def test_elastic_phase_toy(smoke):
    cfg = dataclasses.replace(get_config("musicgen-medium").reduced(),
                              n_layers=2)
    res = smoke.elastic_phase(cfg=cfg, seq_len=32, batch=8,
                              steps_per_stage=2)
    assert [s["label"] for s in res["stages"]] == \
        ["reference", "start", "grow +2", "shrink -2"]
    assert res["events"] == ["rebind", "grow", "rebind", "shrink", "rebind"]
    assert len(res["losses"]) == 6
    assert res["inputs"] == ["codes", "cond", "cond_mask", "labels"]


def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main([]) == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out and "platform=cpu" in out
