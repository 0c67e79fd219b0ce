"""What one run of one cell needs: its entry in ``BENCHMARK.json``, the
files found by name (configuration, traffic, limits, metric readers),
and the run context a driver measures with."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from .instrument import CompileCounter, Spans

BENCH = Path(__file__).resolve().parent.parent          # <checkout>/bench
ROOT = BENCH.parent                                     # <checkout>
CACHE = ROOT / ".bench_cache"                           # gitignored
JAX_CACHE = ROOT / ".jax_cache"     # the checkout's one compile cache


def use_program() -> None:
    """Put the system under test (``<checkout>/src``) on the import path."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class CellSpec:
    workload: dict                  # the entry of BENCHMARK.json
    config: dict                    # bench/configs/<config>.json
    traffic: dict                   # bench/traffic/<traffic>.json
    limits: dict                    # bench/limits/<workload>.json
    end_to_end: List[dict]          # metrics this cell reports, trace 0
    per_layer: List[dict]           # metrics this cell reports, trace 1


def load_cell(name: str, root: Path = ROOT) -> CellSpec:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = load_json(root / conf["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = load_json(root / "bench" / "limits" / f"{name}.json")
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return CellSpec(w, config, traffic, limits, e2e, per_layer)


def load_reader(metric: str, root: Path = ROOT) -> Callable:
    """``bench/metrics/<metric>.py``'s ``read``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Check:
    """One number compared, beside its limit (value <= limit passes;
    None, nothing to compare, fails)."""
    value: Optional[float]
    limit: float

    @property
    def ok(self) -> bool:
        return self.value is not None and self.value <= self.limit


@dataclass
class Outcome:
    """What a driver hands back after its window and its checks."""
    metrics: Dict[str, float]               # end-to-end, by name
    attempted: int
    failed: int
    checks: Dict[str, Check]
    memory_peak_bytes: Optional[int]
    lines: List[str] = field(default_factory=list)    # printed first


class RunContext:
    """Seed, window length and tracing of one run, the spans and
    counters its wrappers fill, and the profiler around its window."""

    def __init__(self, cell: CellSpec, seed: int, seconds: float,
                 trace: bool, t_process: float, devices=None):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.t_process = t_process
        self.devices = devices if devices is not None else jax.devices()
        self.spans = Spans(keep=("pass",))
        self.compiles = CompileCounter()
        self.extra: Dict[str, Any] = {}     # driver facts for the readers
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.compiles_in_window: Optional[int] = None
        self.reduced = None                 # harness.trace.Reduced
        self.peaks = None
        self.trace_dir = CACHE / "trace" / cell.workload["name"]

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it opens; spans and
        counters start afresh; the profiler runs around it when
        tracing.  The driver ends it when ``--seconds`` have passed."""
        self.spans.reset()
        n0 = self.compiles.count
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
        t0 = time.perf_counter()
        self.setup_s = t0 - self.t_process
        try:
            with self.spans.span("window"):
                yield t0
        finally:
            self.window_s = time.perf_counter() - t0
            self.compiles_in_window = self.compiles.count - n0
            self.spans.recording = False
            if self.trace:
                jax.profiler.stop_trace()

    def elapsed(self, t0: float) -> float:
        return time.perf_counter() - t0

    def reduce_trace(self) -> Tuple[Optional[float], Optional[float]]:
        from .trace import find_xplane, reduce_xplane
        path = find_xplane(str(self.trace_dir))
        if path is None:
            return None, None
        self.reduced = reduce_xplane(path)
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        return self.reduced.busy_s(), self.reduced.window_s


def compile_cache_dir() -> Path:
    """JAX's persistent compilation cache for every run in this
    checkout: one fixed path, so that only a cell's first run compiles.
    It is the directory the program's own ``enable_compile_cache``
    falls back to, so the programs are cached once."""
    os.makedirs(JAX_CACHE, exist_ok=True)
    return JAX_CACHE
