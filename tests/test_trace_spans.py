"""Host spans and counters inside the scheduling plane.

A ``SpanCollector`` attached through ``SchedulerInstance.span_collector``
receives one record per span, each on ``time.perf_counter()`` with its
parent on the same thread; detached, span sites record nothing.  The
always-on counters agree with the event stream."""
import os
import subprocess
import sys
from collections import Counter
from pathlib import Path

import pytest

from repro.core import (EasyBackfill, EventType, Instance, Jobspec,
                        SimClock, SpanCollector, build_cluster, flatgraph)
from repro.core.flatgraph import FLAT_MIN_VERTICES

# every span the scheduling plane opens on an exact-EASY site with the
# flat mirror and the device scan path
SPANS = {"queue.step", "queue.submit", "queue.finish", "release",
         "policy.backfill", "policy.ledger", "match", "alloc",
         "flat.sync", "flat.scan", "scan.prep", "scan.device",
         "flat.sweep", "events.subscribers"}


def _site():
    g = build_cluster(nodes=32, sockets_per_node=2, cores_per_socket=8)
    assert g.num_vertices >= FLAT_MIN_VERTICES
    return Instance(graph=g, name="s", clock=SimClock(),
                    policy=EasyBackfill())


def _drive(inst):
    """A blocked head with backfill behind it, job ends, a cancel."""
    big = Jobspec.hpc(nodes=24, sockets=48, cores=384)
    small = Jobspec.hpc(nodes=2, sockets=4, cores=32)
    first = inst.submit(big, walltime=100.0)
    inst.step()
    inst.submit(big, walltime=100.0)
    for _ in range(4):
        inst.submit(small, walltime=50.0)
    inst.step()
    inst.clock.set(60.0)
    inst.cancel(first.jobid)
    inst.step()


@pytest.fixture
def device_path(monkeypatch):
    """Take the device path on the CPU (the kernel interpreted, the
    sweep jitted), so the scan's device half runs."""
    monkeypatch.setattr(flatgraph, "on_device", lambda: True)


def test_detached_records_nothing(device_path):
    inst = _site()
    col = SpanCollector()
    inst.scheduler.span_collector = col
    inst.step()
    assert col.recorded == 1        # the one pass
    inst.scheduler.span_collector = None
    assert inst.scheduler.graph.span_collector is None
    assert inst.queue.eventlog.span_collector is None
    _drive(inst)
    assert col.recorded == 1 and len(col) == 1
    assert inst.queue.n_passes == 4


def test_import_core_imports_no_jax():
    src = str(Path(__file__).resolve().parents[1] / "src")
    code = "import sys, repro.core; print('jax' in sys.modules)"
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True,
                         env=dict(os.environ, PYTHONPATH=src))
    assert out.stdout.strip() == "False"


def test_every_span_nested_on_one_clock(device_path):
    inst = _site()
    col = SpanCollector()
    inst.scheduler.span_collector = col
    _drive(inst)
    inst.scheduler.span_collector = None
    recs = col.drain()
    names = Counter(r["name"] for r in recs)
    assert SPANS <= set(names), SPANS - set(names)
    by_id = {r["id"]: r for r in recs}
    assert len(by_id) == len(recs)
    for r in recs:
        assert r["dur"] >= 0
        p = r["parent"]
        if p is None:
            continue
        par = by_id[p]
        assert par["t0"] <= r["t0"]
        assert r["t0"] + r["dur"] <= par["t0"] + par["dur"]
    # where each span sits
    parent_name = {r["id"]: by_id[r["parent"]]["name"] for r in recs
                   if r["parent"] is not None}
    for r in recs:
        if r["name"] in ("scan.prep", "scan.device"):
            assert parent_name[r["id"]] == "flat.scan"
        elif r["name"] == "release":
            assert parent_name[r["id"]] == "queue.finish"
        elif r["name"] in ("queue.step", "queue.submit"):
            assert r["parent"] is None
    assert names["queue.step"] == inst.queue.n_passes == 3
    rel = [r for r in recs if r["name"] == "release"]
    assert all(r["ok"] and r["n_paths"] > 0 and r["stages"] == {}
               for r in rel)


def test_counters_agree_with_the_event_stream(device_path):
    inst = _site()
    events = []
    inst.subscribe(events.append)
    _drive(inst)
    q, s = inst.queue, inst.scheduler
    starts = sum(1 for e in events if e.type is EventType.START)
    # the two large jobs start as the head; the four small ones jump
    # the blocked second one
    assert starts == 6 and q.n_backfilled == 4
    assert 0 < s.n_match_hits <= s.n_matches
    assert s.n_match_hits == starts
    f = inst.scheduler.graph.flat()
    assert f.n_scans > 0 and f.n_scan_unique <= f.n_scan_rows
    assert f.scan_h2d_bytes > 0 and f.scan_d2h_bytes > 0


def test_spans_feed_the_metrics_aggregator():
    from repro.core import MetricsAggregator
    inst = _site()
    col = SpanCollector()
    inst.scheduler.span_collector = col
    _drive(inst)
    summ = MetricsAggregator().consume_spans(col)
    assert summ["release"]["n"] == 5      # a cancel, four completions
    assert summ["queue.step"]["n"] == 3


def test_annotations_on_the_profiler_host_plane(tmp_path, device_path):
    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData
    inst = _site()
    inst.scheduler.span_collector = SpanCollector()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _drive(inst)
    finally:
        jax.profiler.stop_trace()
    inst.scheduler.span_collector = None
    path = next(tmp_path.rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(path))
    names = {ev.name for plane in pd.planes if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events}
    assert {"repro." + n for n in SPANS} <= names


def test_string_log_is_gone():
    inst = _site()
    assert not hasattr(inst.queue, "events")
    assert not hasattr(inst.queue, "_log")
    assert not hasattr(inst.queue, "max_events")
