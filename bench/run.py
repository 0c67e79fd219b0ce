"""Run one benchmark cell once and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names its configuration, traffic
and chips; the files are found by those names under ``bench/``.  The
run makes its inputs and weights from ``--seed``, warms up every shape
(set-up), measures for ``--seconds``, then checks what the timed path
produced against the plain reference.  The last line of standard output
is one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared beside its limit); the checks are also the last lines
of standard error.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiled run.

A run needs a TPU with at least the cell's chips: with none it exits
with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import ctypes  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

NO_CHIP = 3


def _device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, t_process: float = None,
             cell=None, driver_kwargs=None, on_extra=None) -> dict:
    """One run; returns the result object.  ``require_chip=False``
    (tests) skips the look for a TPU, the peaks table and the persistent
    compile cache; ``cell`` may
    replace the loaded :class:`CellSpec` (smaller sizes in tests)."""
    import jax
    from harness.cell import RunContext, compile_cache_dir, load_cell, \
        load_reader
    from harness.peaks import peaks_for

    cell = cell or load_cell(workload)
    devices = jax.devices()
    chips = cell.workload["chips"]
    info = _device_info(devices)
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']} (cell asks for {chips})", flush=True)
    if require_chip and (info["platform"] != "tpu" or len(devices) < chips):
        print(f"{workload}: needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {info['platform']} device(s)",
              file=sys.stderr)
        raise SystemExit(NO_CHIP)
    if require_chip:
        jax.config.update("jax_compilation_cache_dir",
                          str(compile_cache_dir()))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    ctx = RunContext(cell, seed, seconds, trace,
                     T_PROCESS if t_process is None else t_process,
                     devices=devices[:chips])
    if require_chip:
        ctx.peaks = peaks_for(info["kind"])
    driver = importlib.import_module(f"harness.{cell.traffic['driver']}")
    with ctx.compiles.installed():
        out = driver.run(ctx, **(driver_kwargs or {}))

    if on_extra is not None:
        on_extra(ctx.extra)
    device = dict(info, memory_peak_bytes=out.memory_peak_bytes)
    result = {"correct": all(c.ok for c in out.checks.values()),
              "attempted": out.attempted, "failed": out.failed}
    for line in out.lines:
        print(line, flush=True)
    print(f"set-up {ctx.setup_s:.3f} s; window {ctx.window_s:.3f} s; "
          f"compiles in the window: {ctx.compiles_in_window}", flush=True)
    if trace:
        busy, win = ctx.reduce_trace()
        device.update(busy_s=busy, window_s=win)
        if ctx.reduced is not None:
            for name, (k, sec) in sorted(ctx.reduced.module_totals().items()):
                print(f"device time of {name}: {k} runs, {sec!r} s"
                      + (f" ({1e3 * sec / k!r} ms each)" if k else ""),
                      flush=True)
        metrics = {}
        for m in cell.per_layer:
            val = load_reader(m["name"])(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        if ctx.reduced is not None:
            result["breakdown"] = {"device_ops": ctx.reduced.top_ops(10),
                                   "idle_gaps": ctx.reduced.idle_gaps(10)}
    else:
        vals = dict(out.metrics, setup_s=ctx.setup_s)
        result["metrics"] = {m["name"]: {"value": vals[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end if m["name"] in vals}
        result["device"] = device
    result["checks"] = {k: {"value": c.value, "limit": c.limit}
                        for k, c in out.checks.items()}
    return result


M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3     # glibc's mallopt params


def fix_malloc_thresholds() -> None:
    """Fix glibc's mmap and trim thresholds first thing in a run.

    By default glibc raises its mmap threshold (128 KiB at start) to the
    size of a large mapped block once that block is freed.  A compile
    frees such blocks and a cached program does not, so a run that
    compiled kept its numpy arrays of 0.1-3 MB on the heap while a run
    from the cache mapped and faulted them in on every call: the
    feasibility scan's host side took 9 ms a call against 17 ms.  With
    the mmap threshold fixed at the top of glibc's own range, every run
    allocates alike."""
    libc = ctypes.CDLL(None)
    if not (libc.mallopt(M_MMAP_THRESHOLD, 32 << 20)
            and libc.mallopt(M_TRIM_THRESHOLD, 256 << 20)):
        raise SystemExit("mallopt refused the malloc thresholds")


def main(argv=None) -> int:
    fix_malloc_thresholds()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
