"""Training cells: a model trained through the program's ``ElasticRuntime``
on an allocation of the ``Instance`` queue that grows and shrinks.

The job is submitted for ``start_chips`` chips of a one-node fleet of
``fleet_chips`` (built as ``chip_smoke.py`` builds it) and trains in a
closed loop: each step starts when the last one's loss is on the host.
Every batch is made from the seed by this file: codes of each codebook
uniform over its vocabulary, laid out in the delay pattern, and text
states of ``text_len_min``-``text_len_max`` tokens padded to the
maximum.  At the first step boundary after ``grow_at`` of the window the
job grows by ``resize_chips`` through the queue (MATCHGROW, rebind,
reshard), and after ``shrink_at`` it shrinks by as many.  Set-up binds,
initializes from the seed, runs one step on each mesh size through the
same grow and shrink, then returns to the seed's initial state on
``start_chips`` chips, so that no resize in the window compiles.

* ``tokens_per_s``: every codebook target trained in the window (the
  delay pattern's filler targets left out) over the window's wall time,
  resizes included.

Checks (``bench/limits/<cell>.json``):

* at each resize (the window's two and the check's grow), a
  position-weighted checksum of every parameter and AdamW leaf (bit
  pattern as uint32 times its flat index plus one, summed mod 2**32:
  exact in any order of reduction) before and after:
  ``reshard_mismatch`` counts the leaves that differ, and
  ``resize_faults`` the resizes that failed or after which the state
  does not lie on exactly the chips of the new allocation's mesh;
* after the window, one more step with a held-out batch on each mesh
  size, against the float32 reference (``reference.musicgen_lm``: its
  forward and backward on a chip the shrink freed, its AdamW step and
  the gaps on the host, in numpy): first on the final state on
  ``start_chips`` chips, then, after a grow back through the queue, on
  the big mesh.  For each, ``grad_gap`` (the step's clipped gradient,
  read from AdamW's first moment before and after, relative by norm)
  and ``update_gap`` (the parameters' change, relative by norm); the big
  mesh's carry its size as a suffix (``grad_gap.4``) and the same
  limits.  ``loss_gap`` (relative) is printed beside them and decides
  nothing: the fp8 control hardly moves it, so no limit lies between
  the program's readings and the control's (PERF.md, section 2).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .cell import Check, Outcome, RunContext, use_program
from .instrument import peak_memory_bytes
from .traffic import jax_key_seed

HELD_OUT = 2 ** 31          # batch index of the check's batch
WARMUP = 2 ** 31 + 1        # batch indices of set-up's steps

# the configuration file's keys, and the program's ArchConfig fields
# they must equal
_ARCH_FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
                "num_attention_heads": "n_heads", "head_dim": "hd",
                "ffn_dim": "d_ff", "vocab_size": "vocab",
                "num_codebooks": "n_codebooks",
                "text_encoder_hidden_size": "cond_dim",
                "layer_norm_eps": "norm_eps", "compute_dtype": "dtype"}


def program_arch(c: dict):
    """The program's architecture for configuration ``c``, at the file's
    depth, checked field by field against the file.  A program without
    a codebook model that cross-attends fails here, before any chip
    work."""
    use_program()
    from repro.configs.registry import get_config
    arch = get_config(c["program_arch"])
    if getattr(arch, "n_codebooks", 0) != c["num_codebooks"] or \
            getattr(arch, "cond_dim", 0) != c["text_encoder_hidden_size"]:
        raise SystemExit(f"{c['program_arch']}: the program has no model "
                         f"of {c['num_codebooks']} codebooks with "
                         f"cross-attention to width "
                         f"{c['text_encoder_hidden_size']}")
    arch = dataclasses.replace(arch, n_layers=c["num_hidden_layers"])
    for key, attr in _ARCH_FIELDS.items():
        if getattr(arch, attr) != c[key]:
            raise SystemExit(f"{c['program_arch']}: {attr}="
                             f"{getattr(arch, attr)!r} but the "
                             f"configuration file has {key}={c[key]!r}")
    if (arch.norm, arch.mlp_act, arch.rope) != \
            ("layernorm", "gelu_exact", "abs_sin"):
        raise SystemExit(f"{c['program_arch']}: not pre-LN, exact GELU "
                         f"and sinusoidal positions")
    return arch


def program_opt(c: dict):
    from repro.optim.adamw import OptConfig
    o = c["optimizer"]
    return OptConfig(kind="adamw", lr=o["lr"], b1=o["betas"][0],
                     b2=o["betas"][1], eps=o["eps"],
                     weight_decay=o["weight_decay"], clip_norm=o["grad_clip"],
                     warmup=o["warmup_steps"], total_steps=o["total_steps"])


# ---------------------------------------------------------------------- #
# inputs from the seed
# ---------------------------------------------------------------------- #
def make_batch(c: dict, t: dict, seed: int, index: int) -> Dict[str, np.ndarray]:
    """Batch ``index``: codes [b, K, s] in the delay pattern (codebook k
    shifted right by k frames, its first k positions the special token
    ``vocab_size``), targets [b, K, s] (the next position's codes), text
    states [b, T, C] zero past each row's length, and their mask."""
    rng = np.random.default_rng([seed, index])
    b, s, k, v = t["batch"], t["frames"], c["num_codebooks"], c["vocab_size"]
    raw = rng.integers(0, v, (b, k, s + 1), dtype=np.int32)
    seq = np.full_like(raw, v)
    for i in range(k):
        seq[:, i, i:] = raw[:, i, :s + 1 - i]
    tmax = t["text_len_max"]
    lengths = rng.integers(t["text_len_min"], tmax + 1, b)
    mask = np.arange(tmax)[None, :] < lengths[:, None]
    cond = rng.standard_normal((b, tmax, c["text_encoder_hidden_size"]),
                               dtype=np.float32) * mask[..., None]
    return {"codes": seq[:, :, :-1], "labels": seq[:, :, 1:], "cond": cond,
            "cond_mask": mask}


def targets(c: dict, batch: Dict[str, np.ndarray]) -> int:
    return int(np.sum(batch["labels"] != c["vocab_size"]))


# ---------------------------------------------------------------------- #
# model FLOPs (no recomputation counted)
# ---------------------------------------------------------------------- #
def train_step_flops(c: dict, batch: int, frames: int, text_len: int) -> float:
    """FLOPs a training step needs: 3x the forward (forward, and the
    backward's two products per forward product).  Forward: 2 per
    multiply-add through the weights, audio positions through the self-
    and cross-attention's Q and O, the FFN and the K heads, text tokens
    (padded length) through the cross-attention's K and V and the
    projection; attention products QK^T and PV, causal over the audio
    (position i sees i + 1) and full over the text."""
    e, f, L = c["hidden_size"], c["ffn_dim"], c["num_hidden_layers"]
    k, v, cd = c["num_codebooks"], c["vocab_size"], \
        c["text_encoder_hidden_size"]
    per_pos = L * (4 * e * e + 2 * e * e + 2 * e * f) + k * e * v
    per_text = L * 2 * e * e + cd * e
    mm = 2.0 * (per_pos * batch * frames + per_text * batch * text_len)
    attn = L * batch * (2.0 * e * frames * (frames + 1)
                        + 4.0 * e * frames * text_len)
    return 3.0 * (mm + attn)


# ---------------------------------------------------------------------- #
# the run
# ---------------------------------------------------------------------- #
def _leaf_checksum(x):
    """Position-weighted sum of a leaf's bit patterns, mod 2**32: the flat
    index from iotas in the leaf's own shape, so that a sharded leaf is
    not gathered."""
    idx = jnp.ones(x.shape, jnp.uint32)
    stride = 1
    for d in reversed(range(x.ndim)):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, x.shape, d) \
            * jnp.uint32(stride)
        stride *= x.shape[d]
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.sum(u * idx, dtype=jnp.uint32)


@jax.jit
def state_checksums(state):
    return jnp.stack([_leaf_checksum(x)
                      for x in jax.tree_util.tree_leaves(state)])


def _rel_gap(a, b) -> float:
    """||a - b|| / ||b|| over every leaf of two trees of host arrays."""
    from reference.musicgen_lm import sum_blocks
    num = sum_blocks(lambda x, y: np.sum(np.square(x - y)), a, b)
    den = sum_blocks(lambda y: np.sum(np.square(y)), b)
    return math.sqrt(num / den) if den > 0 else float("inf")


def run(ctx: RunContext, control: bool = False) -> Outcome:
    """``control`` also reads the control on the same held-out step: the
    fp8 reference's loss, clipped gradient and update against the
    float32 reference's (``bench/control.py``)."""
    from reference import musicgen_lm
    from reference.musicgen_lm import map_blocks

    cell = ctx.cell
    c, t = cell.config, cell.traffic
    arch = program_arch(c)
    from repro.core import Instance, SchedulerInstance, SpanCollector
    from repro.core.graph import build_tpu_fleet
    from repro.models.config import ShapeConfig
    from repro.runtime.elastic import ElasticRuntime

    shape = ShapeConfig("elastic", t["frames"], t["batch"], "train",
                        cond_len=t["text_len_max"])
    fleet = build_tpu_fleet(pods=1, racks_per_pod=1, nodes_per_rack=1,
                            chips_per_node=t["fleet_chips"])
    rt = ElasticRuntime(Instance(SchedulerInstance("host", fleet)), arch,
                        shape, chip_type="chip", opt=program_opt(c))
    small, big = t["start_chips"], t["start_chips"] + t["resize_chips"]
    key = jax.random.wrap_key_data(np.asarray(jax_key_seed(ctx.seed),
                                              np.uint32))
    resizes: List[Dict] = []

    def resize(kind: str) -> Dict:
        """Grow or shrink through the queue, with the state's checksums
        before and after and the devices it lies on after."""
        before = np.asarray(state_checksums((rt.params, rt.opt_state)))
        with ctx.spans.span("resize"):
            ok = getattr(rt, kind)(t["resize_chips"])
        after = np.asarray(state_checksums((rt.params, rt.opt_state)))
        held = rt.chips_allocated()
        want = {d.id for d in jax.devices()[:held]}
        on = [{d.id for d in x.devices()} for x in
              jax.tree_util.tree_leaves((rt.params, rt.opt_state))]
        return {"kind": kind, "ok": bool(ok),
                "mismatch": int(np.sum(before != after)),
                "misplaced": not ok or any(s != want for s in on) or
                rt.mesh.size != held}

    def train_step(batch) -> float:
        return float(rt.step(batch)["loss"])

    # -- set-up: every mesh size steps once, then the seed's state -------- #
    assert rt.allocate(small), f"allocating {small} chips failed"
    rt.prepare([small, big])
    rt.bind(key)
    warm = make_batch(c, t, ctx.seed, WARMUP)
    train_step(warm)
    for kind in ("grow", "shrink"):
        assert resize(kind)["ok"], f"set-up: the {kind} failed"
        train_step(warm)
    rt.init_state(key)
    jax.block_until_ready((rt.params, rt.opt_state))
    del warm

    col = None
    if ctx.trace:                   # the program's spans, for the readers
        col = rt.scheduler.span_collector = SpanCollector()
    n_builds0, n_resizes0, bytes0 = \
        rt.n_step_builds, rt.n_resizes, rt.reshard_bytes
    losses: List[float] = []
    meshes: List[int] = []
    chip_s = 0.0
    with ctx.window() as t0:
        last = t0
        nxt = make_batch(c, t, ctx.seed, 0)
        while ctx.elapsed(t0) < ctx.seconds:
            el = ctx.elapsed(t0)
            due = [k for k, at in (("grow", t["grow_at"]),
                                   ("shrink", t["shrink_at"]))
                   if el >= at * ctx.seconds
                   and k not in {r["kind"] for r in resizes}]
            if due:
                # the job holds the larger allocation from the grow's
                # request to the shrink's end
                now = time.perf_counter()
                if due[0] == "grow":
                    chip_s += rt.mesh.size * (now - last)
                    last = now
                resizes.append(resize(due[0]))
                if due[0] == "shrink":
                    now = time.perf_counter()
                    chip_s += big * (now - last)
                    last = now
            with ctx.spans.span("step"):
                batch, step_mesh = nxt, rt.mesh.size
                metrics = rt.step(batch)
                nxt = make_batch(c, t, ctx.seed, len(losses) + 1)
                losses.append(float(metrics["loss"]))
            meshes.append(step_mesh)
        chip_s += rt.mesh.size * (time.perf_counter() - last)
    window_line = (
        f"steps in the window: {len(losses)} (on {sorted(set(meshes))} "
        f"chips: {[meshes.count(n) for n in sorted(set(meshes))]}); "
        f"resizes {[r['kind'] for r in resizes]}; step builds in the "
        f"window {rt.n_step_builds - n_builds0}; resizes counted "
        f"{rt.n_resizes - n_resizes0}; reshard bytes "
        f"{rt.reshard_bytes - bytes0}")
    memory = peak_memory_bytes(ctx.devices)
    per_chip = [peak_memory_bytes([d]) for d in ctx.devices]
    window_spans = []
    if col is not None:
        rt.scheduler.span_collector = None
        w0 = ctx.t_process + ctx.setup_s
        window_spans = [s for s in col.drain()
                        if w0 <= s["t0"] < w0 + ctx.window_s]

    per_step = targets(c, make_batch(c, t, ctx.seed, 0))
    tokens = per_step * len(losses)
    flops = train_step_flops(c, t["batch"], t["frames"], t["text_len_max"])
    ctx.extra.update(train_flops_step=flops, step_meshes=meshes,
                     chip_seconds=chip_s, program_spans=window_spans)

    # -- check: one more step on each mesh size, against the reference -- #
    spare = [d for d in ctx.devices if d not in set(rt.mesh.devices.flat)]
    opt = c["optimizer"]
    b1 = np.float32(opt["betas"][0])

    def reference(old, step: int, batch, fp8: bool) -> Dict:
        """The reference's loss, clipped gradient and update from the
        host state ``old``, its forward and backward on a spare chip."""
        rloss, rgrad = musicgen_lm.loss_and_grads(
            c, old[0], batch, spare[-1], t["check_rows"], fp8=fp8)
        rgrad = jax.device_get(rgrad)
        rnew = musicgen_lm.adamw_step(opt, old[0], rgrad, old[1].mu,
                                      old[1].nu, step)
        return {"loss": rloss, "grad": musicgen_lm.clipped(opt, rgrad),
                "update": map_blocks(np.subtract, rnew, old[0])}

    def compare(got: Dict, ref: Dict) -> Dict[str, float]:
        return {"loss_gap": abs(got["loss"] - ref["loss"]) / abs(ref["loss"]),
                "grad_gap": _rel_gap(got["grad"], ref["grad"]),
                "update_gap": _rel_gap(got["update"], ref["update"])}

    def checked_step(index: int, move=None) -> Dict:
        """Step the held-out batch ``index`` from the bound state (after
        ``move``, a resize) and compare the step with the reference's
        from the same state: the program's clipped gradient read back
        from the first moment (m1 = b1 m0 + (1 - b1) g), its change of
        the parameters."""
        t_check = time.perf_counter()
        batch = make_batch(c, t, ctx.seed, index)
        old = jax.device_get((rt.params, rt.opt_state))
        step = int(old[1].step) + 1
        ref = reference(old, step, batch, False)
        out = {"step": step}
        if control and move is None:
            ctx.extra["control_gap"] = compare(
                reference(old, step, batch, True), ref)
        if move is not None:
            move()
        out["loss"] = train_step(batch)
        out["mesh"] = rt.mesh.size
        new = jax.device_get((rt.params, rt.opt_state.mu))
        got = {"loss": out["loss"],
               "grad": map_blocks(lambda m1, m0: (m1 - b1 * m0) / (1 - b1),
                                  new[1], old[1].mu),
               "update": map_blocks(np.subtract, new[0], old[0])}
        out.update(ref_loss=ref["loss"], gaps=compare(got, ref),
                   seconds=time.perf_counter() - t_check)
        return out

    # the final state on the small mesh, then a grow back to the big one
    # and a step of its compiled program
    small_check = checked_step(HELD_OUT)
    big_check = checked_step(HELD_OUT + 1,
                             move=lambda: resizes.append(resize("grow")))

    lim = cell.limits
    mismatch = sum(r["mismatch"] for r in resizes)
    misplaced = sum(r["misplaced"] for r in resizes)
    checks = {"reshard_mismatch": Check(mismatch, lim["reshard_mismatch"]),
              "resize_faults": Check(misplaced, lim["resize_faults"])}
    for suffix, chk in (("", small_check), (f".{big}", big_check)):
        for name in ("grad_gap", "update_gap"):
            checks[name + suffix] = Check(chk["gaps"][name], lim[name])
    failed = sum(not r["ok"] for r in resizes) + \
        sum(not np.isfinite(x) for x in losses)
    lines = [f"{window_line}; targets {tokens}",
             f"losses: first {losses[0]!r}, last {losses[-1]!r}"
             if losses else "losses: none",
             f"memory peak per chip (0..{len(per_chip) - 1}) after the "
             f"window: {per_chip}",
             *[f"check at step {chk['step']} on {chk['mesh']} chips: loss "
               f"{chk['loss']!r} (reference {chk['ref_loss']!r}); " +
               ", ".join(f"{k} {v!r}" for k, v in chk["gaps"].items()) +
               f"; {chk['seconds']:.1f} s" for chk in (small_check, big_check)],
             f"reshard_mismatch {mismatch}, resize_faults {misplaced} over "
             f"{len(resizes)} resizes (the check's grow included)"]
    return Outcome(metrics={"tokens_per_s": tokens / ctx.window_s},
                   attempted=len(losses) + len(resizes), failed=failed,
                   checks=checks, memory_peak_bytes=memory, lines=lines)
