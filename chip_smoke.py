"""Bring-up smoke: both main paths, end to end, on TPU.

  python chip_smoke.py              # one chip: scheduling plane + serving
  python chip_smoke.py --chips 4    # four chips: elastic training only

One process drives every phase and starts no child.  Each phase
asserts its own result; none catches its own failure.  The last line of
standard output is ``{"ok": true, "device": {...}}`` and is printed only
when every phase passed on a TPU.  The numbers printed on the way are
set-up and smoke numbers, not benchmark metrics.

Phases:

* **scheduling** — an LLNL Quartz-sized cluster (2,688 nodes x 2
  sockets x 18 cores, |V| = 104,833) behind one ``Instance`` with exact
  EASY backfill; a seeded backlog that exceeds capacity, then arrivals,
  replayed to completion.  On TPU the batched feasibility scan
  (compiled Pallas kernel) and the aggregate sweep (jitted) run on the
  device; on sampled live states both device halves must answer as
  their numpy twins do.
* **serving** — ``launch/serve.run_serving`` for phi4-mini-3.8b at its
  registered widths and depth with random bf16 weights; the logits of
  the decode-through-cache steps must match one full forward pass.
* **elastic** (``--chips 4``) — ``ElasticRuntime`` training of
  musicgen-medium at published widths (codes of four codebooks in the
  delay pattern, text states and their mask), 2 chips -> grow to 4 ->
  shrink to 2 through the ``Instance`` queue, against the same batches
  on one fixed device.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import random
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.core import (EasyBackfill, Instance, Jobspec,  # noqa: E402
                        SchedulerInstance, SimClock, build_cluster,
                        build_tpu_fleet, flatgraph)
from repro.data.pipeline import DataConfig, SyntheticTokenPipeline  # noqa: E402
from repro.kernels import feasibility  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import run_serving  # noqa: E402
from repro.models.config import ShapeConfig  # noqa: E402
from repro.models.transformer import forward  # noqa: E402
from repro.optim.adamw import OptConfig  # noqa: E402
from repro.runtime.elastic import ElasticRuntime  # noqa: E402

# LLNL Quartz: 2,688 nodes x 2 sockets x 18 cores, a machine Flux schedules
QUARTZ = dict(nodes=2688, sockets_per_node=2, cores_per_socket=18)

# Decode-through-cache vs full-forward logits, as a fraction of the
# largest reference logit.  Activations and the KV cache are bfloat16
# (8 significant bits, relative rounding up to 2**-8 = 0.0039 per op);
# the two paths round at different points (one token at a time against
# the cache vs all positions in one matmul) and the differences compound
# through the residual stream of every layer.  0.05 is ~13 bf16 ulps of
# the largest logit: far above that rounding, far below a wrong cache
# position or mask, which moves logits by their own size.
SERVE_LOGIT_TOL = 0.05

# Elastic vs one-device losses, relative.  Parameters and optimizer
# state are float32; only the order of the gradient reductions differs
# between a 1-, 2- and 4-device mesh, so losses agree to float32
# rounding amplified by a few AdamW steps.
ELASTIC_LOSS_RTOL = 1e-3

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ---------------------------------------------------------------------- #
# instrumentation (script-side: counts calls, changes no behaviour)
# ---------------------------------------------------------------------- #
@contextlib.contextmanager
def _compile_seconds(acc: dict):
    """Sum XLA backend-compile seconds into ``acc["compile_s"]``."""
    def listener(event, duration, **_):
        if event == COMPILE_EVENT:
            acc["compile_s"] += duration
    acc.setdefault("compile_s", 0.0)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield acc
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def _platforms(out) -> set:
    return {d.platform for leaf in jax.tree_util.tree_leaves(out)
            for d in leaf.devices()}


@contextlib.contextmanager
def _device_calls(acc: dict):
    """Count calls into the scheduling plane's device path: the Pallas
    feasibility kernel (compiled or interpreted, and whether its
    lowering holds a Mosaic ``tpu_custom_call``) and the jitted
    aggregate sweep, with the platforms their outputs live on."""
    acc.update(kernel_compiled=0, kernel_interpret=0, mosaic=False,
               sweep_calls=0, platforms=set())
    kernel = feasibility._feasible_pallas
    sweep_fn = flatgraph._sweep_fn

    def counted_kernel(*args, **kwargs):
        out = kernel(*args, **kwargs)
        if kwargs.get("interpret", True):
            acc["kernel_interpret"] += 1
        else:
            if not acc["kernel_compiled"]:
                text = kernel.lower(*args, **kwargs).as_text()
                acc["mosaic"] = "tpu_custom_call" in text
            acc["kernel_compiled"] += 1
        acc["platforms"] |= _platforms(out)
        return out

    def counted_sweep_fn():
        sweep = sweep_fn()

        def run(*args):
            out = sweep(*args)
            acc["sweep_calls"] += 1
            acc["platforms"] |= _platforms(out)
            return out
        return run

    feasibility._feasible_pallas = counted_kernel
    flatgraph._sweep_fn = counted_sweep_fn
    try:
        yield acc
    finally:
        feasibility._feasible_pallas = kernel
        flatgraph._sweep_fn = sweep_fn


def _peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------- #
# phase: scheduling plane at site scale
# ---------------------------------------------------------------------- #
def site_trace(nodes: int, cores_per_socket: int, backlog: int,
               arrivals: int, seed: int):
    """Seeded site trace: ``backlog`` jobs at t=0, then ``arrivals``
    more with exponential gaps.  Jobs take 2**k whole nodes, k uniform
    up to a quarter of the cluster (1-512 nodes on Quartz), with half or
    full sockets of cores; so the backlog far exceeds capacity and the
    pending window stays in the hundreds."""
    rng = random.Random(seed)
    kmax = (nodes // 4).bit_length() - 1
    specs = {}
    t = 0.0
    for i in range(backlog + arrivals):
        if i >= backlog:
            t += rng.expovariate(1 / 60.0)
        n = 2 ** rng.randint(0, kmax)
        c = rng.choice([cores_per_socket // 2, cores_per_socket])
        spec = specs.get((n, c))
        if spec is None:
            spec = specs[(n, c)] = Jobspec.hpc(nodes=n, sockets=2 * n,
                                               cores=2 * n * c)
        yield {"arrival": t, "jobspec": spec,
               "walltime": rng.uniform(600.0, 7200.0)}


def _check_device_parity(flat, reqs) -> None:
    """On the live state, the device halves (the feasibility kernel,
    compiled on a TPU and interpreted elsewhere; the jitted sweep) equal
    their numpy twins, and a from-scratch sweep equals the
    incrementally kept table."""
    flat.sync()
    _, ops = flat.scan_operands(reqs)
    got = feasibility.run_packed(feasibility.pack_inputs(*ops))
    want = flatgraph.batched_candidate_mask(*ops)
    assert np.array_equal(got[:want.shape[0], :flat.n] != 0, want), \
        "feasibility: device != numpy"
    own, parent, levels = flat.own_counts(), flat.parent[:flat.n], \
        flat._levels
    got = flatgraph._aggregate_sweep_jax(own, parent, levels)
    want = flatgraph._aggregate_sweep_numpy(own, parent, levels)
    assert np.array_equal(got, want), "aggregate sweep: device != numpy"
    assert np.array_equal(want, flat.agg[:flat.n, :own.shape[1]]), \
        "sweep != live table"


def scheduling_phase(nodes: int = QUARTZ["nodes"],
                     sockets_per_node: int = QUARTZ["sockets_per_node"],
                     cores_per_socket: int = QUARTZ["cores_per_socket"],
                     backlog: int = 256, arrivals: int = 128,
                     probe_rate: float = 0.05, seed: int = 0) -> dict:
    t_start = time.perf_counter()
    g = build_cluster(nodes=nodes, sockets_per_node=sockets_per_node,
                      cores_per_socket=cores_per_socket)
    clock = SimClock()
    inst = Instance(graph=g, name="site", clock=clock, policy=EasyBackfill())
    n_vertices = g.num_vertices
    build_s = time.perf_counter() - t_start
    print(f"[sched] cluster {nodes}x{sockets_per_node}x{cores_per_socket} "
          f"|V|={n_vertices} built in {build_s:.1f}s", flush=True)

    probe_rng = random.Random(seed + 1)
    trace = list(site_trace(nodes, cores_per_socket, backlog, arrivals,
                            seed))
    every_shape = list({id(r): r for e in trace
                        for r in e["jobspec"].resources}.values())
    res = {"jobs": len(trace), "kicks": 0, "probes": 0,
           "n_vertices": n_vertices, "max_pending": 0}

    def kick(do) -> None:
        do()
        res["kicks"] += 1
        pending = inst.pending()
        res["max_pending"] = max(res["max_pending"], len(pending))
        if res["probes"] == 0 or probe_rng.random() < probe_rate:
            reqs = [r for h in pending for r in h.job.jobspec.resources]
            _check_device_parity(g.flat(), reqs or every_shape)
            res["probes"] += 1

    t0 = time.perf_counter()
    with _compile_seconds(res), _device_calls(res):
        for i, e in enumerate(trace):
            if e["arrival"] > clock.now():
                kick(lambda: inst.advance(e["arrival"] - clock.now()))
            inst.submit(e["jobspec"], walltime=e["walltime"])
            if i >= backlog - 1:        # the t=0 backlog lands in one kick
                kick(inst.step)
        while inst.pending() or inst.running():
            ends = [h.job.end_time for h in inst.running()]
            assert ends, "jobs pending with nothing running: stuck"
            kick(lambda: inst.advance(max(min(ends) - clock.now(), 0.0)))
        res["wall_s"] = time.perf_counter() - t0
        _check_device_parity(g.flat(), every_shape)
        res["probes"] += 1

    s = inst.stats()
    assert s.submitted == len(trace), (s.submitted, len(trace))
    assert s.completed == s.submitted, \
        f"{s.submitted - s.completed} jobs never completed"
    assert inst.scheduler.allocations == {}, "allocations leaked"
    assert g.validate_tree(), "validate_tree failed"
    print(f"[sched] jobs={res['jobs']} completed={s.completed} "
          f"kicks={res['kicks']} max_pending={res['max_pending']} "
          f"parity_probes={res['probes']} "
          f"kernel_calls(compiled={res['kernel_compiled']}, "
          f"interpret={res['kernel_interpret']}) mosaic={res['mosaic']} "
          f"sweep_calls={res['sweep_calls']} "
          f"platforms={sorted(res['platforms'])} "
          f"compile_s={res['compile_s']:.2f} wall_s={res['wall_s']:.2f}",
          flush=True)
    return res


# ---------------------------------------------------------------------- #
# phase: serving at published widths
# ---------------------------------------------------------------------- #
def serving_phase(arch: str = "phi4-mini-3.8b", batch: int = 4,
                  prompt_len: int = 512, gen: int = 32, seed: int = 0,
                  smoke: bool = False) -> dict:
    t_start = time.perf_counter()
    with _compile_seconds({}) as comp:
        out = run_serving(arch, batch=batch, prompt_len=prompt_len,
                          gen=gen, smoke=smoke, seed=seed)
    model, params = out["model"], out["params"]
    cfg = model.cfg
    assert cfg.frontend == "token", "the reference replays token ids"
    leaves = jax.tree_util.tree_leaves(params)
    dtypes = {str(leaf.dtype) for leaf in leaves}
    assert dtypes == {cfg.dtype}, dtypes
    n_params = sum(leaf.size for leaf in leaves)
    param_bytes = sum(leaf.nbytes for leaf in leaves)
    peak_serving = _peak_bytes()

    # reference: one full forward over prompt + generated[:-1]; its
    # logits at positions prompt_len-1 .. prompt_len+gen-2 are what the
    # prefill and the gen-1 cached decode steps predicted
    seq = jnp.concatenate([out["prompt"]["tokens"],
                           jnp.asarray(out["tokens"][:, :-1])], axis=1)
    ref_fn = jax.jit(lambda p, t: forward(p, cfg, model.ctx, tokens=t)[0]
                     [:, prompt_len - 1:])
    ref = ref_fn(params, seq)
    got = out["logits"]
    assert got.shape == ref.shape == (batch, gen, cfg.vocab), \
        (got.shape, ref.shape)
    assert bool(jnp.all(jnp.isfinite(got))) and \
        bool(jnp.all(jnp.isfinite(ref))), "non-finite logits"
    err = float(jnp.max(jnp.abs(got - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    agree = float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(ref, -1)))
    res = {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
           "vocab": cfg.vocab, "dtype": cfg.dtype, "n_params": n_params,
           "param_bytes": param_bytes, "max_abs_err": err,
           "max_abs_ref": scale, "argmax_agree": agree,
           "peak_bytes_serving": peak_serving, "peak_bytes": _peak_bytes(),
           "compile_s": comp["compile_s"], "prefill_s": out["prefill_s"],
           "decode_s": out["decode_s"],
           "wall_s": time.perf_counter() - t_start}
    print(f"[serve] {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab} dtype={cfg.dtype} params={n_params} "
          f"({param_bytes} bytes) batch={batch} prompt={prompt_len} "
          f"gen={gen}", flush=True)
    print(f"[serve] decode vs forward: max|diff|={err:.6g} "
          f"max|ref|={scale:.6g} ratio={err / scale:.6g} "
          f"(tol {SERVE_LOGIT_TOL}) argmax agree={agree:.4f}", flush=True)
    print(f"[serve] compile_s={res['compile_s']:.2f} "
          f"prefill_s={res['prefill_s']:.4f} decode_s={res['decode_s']:.4f} "
          f"peak_bytes_serving={peak_serving} peak_bytes={res['peak_bytes']} "
          f"wall_s={res['wall_s']:.2f}", flush=True)
    assert err <= SERVE_LOGIT_TOL * scale, \
        f"decode logits off by {err} (max |ref| {scale})"
    return res


# ---------------------------------------------------------------------- #
# phase: elastic training across chips (--chips 4)
# ---------------------------------------------------------------------- #
def _stage(rt: ElasticRuntime, label: str) -> dict:
    mesh_ids = [d.id for d in rt.mesh.devices.flat]
    held = sorted({d.id for leaf in jax.tree_util.tree_leaves(rt.params)
                   for d in leaf.devices()})
    print(f"[elastic] {label}: chips={rt.chips_allocated()} "
          f"mesh={dict(rt.mesh.shape)} mesh_devices={mesh_ids} "
          f"param_devices={held}", flush=True)
    assert held == sorted(mesh_ids), (held, mesh_ids)
    assert rt.mesh.size == min(rt.chips_allocated(), len(jax.devices()))
    return {"label": label, "mesh_size": rt.mesh.size, "devices": mesh_ids}


def _runtime(cfg, shape, opt, chips: int) -> ElasticRuntime:
    """A training job holding ``chips`` chips of a one-host fleet."""
    fleet = build_tpu_fleet(pods=1, racks_per_pod=1, nodes_per_rack=1,
                            chips_per_node=4)
    rt = ElasticRuntime(Instance(SchedulerInstance("host", fleet)), cfg,
                        shape, chip_type="chip", opt=opt)
    assert rt.allocate(chips), f"allocating {chips} chips failed"
    return rt


def elastic_phase(arch: str = "musicgen-medium", n_layers: int = 16,
                  seq_len: int = 1024, batch: int = 8,
                  steps_per_stage: int = 3, seed: int = 0,
                  cfg=None) -> dict:
    """``cfg`` (default: ``arch`` at published widths, depth cut to
    ``n_layers`` so its AdamW state fits one chip) is trained through
    2 -> 4 -> 2 chips and, on the same batches, on one fixed device."""
    cfg = cfg or dataclasses.replace(get_config(arch), n_layers=n_layers)
    shape = ShapeConfig("elastic", seq_len, batch, "train")
    total = 3 * steps_per_stage
    opt = OptConfig(kind=cfg.optimizer, warmup=2, total_steps=total)
    pipe = SyntheticTokenPipeline(cfg, shape, DataConfig(seed=seed))
    key = jax.random.key(seed)
    print(f"[elastic] {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"params={cfg.n_params()} batch={batch} seq={seq_len}", flush=True)
    t_start = time.perf_counter()

    with _compile_seconds({}) as comp:
        ref = _runtime(cfg, shape, opt, chips=1)
        ref.bind(key)
        stages = [_stage(ref, "reference")]
        ref_losses = [float(ref.step(pipe.batch_at(i))["loss"])
                      for i in range(total)]
        del ref                     # free its device memory
        gc.collect()

        rt = _runtime(cfg, shape, opt, chips=2)
        rt.bind(key)
        stages.append(_stage(rt, "start"))
        losses = []
        for i in range(total):
            if i == steps_per_stage:
                assert rt.grow(2), "grow failed"
                stages.append(_stage(rt, "grow +2"))
            if i == 2 * steps_per_stage:
                assert rt.shrink(2), "shrink failed"
                stages.append(_stage(rt, "shrink -2"))
            losses.append(float(rt.step(pipe.batch_at(i))["loss"]))
    kinds = [e.kind for e in rt.events]
    wall = time.perf_counter() - t_start
    for i, (a, b) in enumerate(zip(losses, ref_losses)):
        print(f"[elastic] step {i}: loss={a:.6f} reference={b:.6f} "
              f"rel_diff={abs(a - b) / abs(b):.3g}", flush=True)
    print(f"[elastic] events={kinds} compile_s={comp['compile_s']:.2f} "
          f"wall_s={wall:.2f} peak_bytes={_peak_bytes()}", flush=True)
    assert all(np.isfinite(losses)), losses
    np.testing.assert_allclose(losses, ref_losses, rtol=ELASTIC_LOSS_RTOL)
    return {"losses": losses, "ref_losses": ref_losses, "stages": stages,
            "events": kinds, "inputs": sorted(pipe.batch_at(0)),
            "compile_s": comp["compile_s"], "wall_s": wall}


# ---------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: scheduling + serving on one chip (default); "
                         "4: elastic training across four chips only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__} platform={dev.platform} "
          f"kind={dev.device_kind} count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform}",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        assert len(devices) >= 4, f"--chips 4 needs 4 devices: {devices}"
        res = elastic_phase(seed=args.seed)
        assert [s["mesh_size"] for s in res["stages"]] == [1, 2, 4, 2], \
            res["stages"]
        assert res["events"].count("grow") == 1 and \
            res["events"].count("shrink") == 1, res["events"]
    else:
        sched = scheduling_phase(seed=args.seed)
        assert sched["n_vertices"] == 104_833, sched["n_vertices"]
        assert sched["kernel_compiled"] >= 1 and sched["mosaic"], sched
        assert sched["kernel_interpret"] == 0, sched
        assert sched["sweep_calls"] >= 1, sched
        assert sched["platforms"] == {"tpu"}, sched["platforms"]
        serve = serving_phase(seed=args.seed)
        assert serve["n_layers"] == 32 and serve["dtype"] == "bfloat16"
        assert serve["peak_bytes"] is not None and \
            serve["peak_bytes"] < 16e9, serve["peak_bytes"]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
