"""Serving cells: a dense decoder LM served greedily in a closed loop of
static batches through the program's ``make_model`` ->
``prefill_step`` / ``serve_step``.

Each batch is ``batch`` requests with ``prompt_len``-token prompts drawn
from the seed; the loop prefills them, then decodes ``gen_len`` tokens,
pulling every step's tokens to the host (as a server streaming them
would), and starts the next batch when one ends.  The window ends at
the first step that finishes after ``--seconds``.

* ``tokens_per_s``: every token produced in the window (a prefill's
  first tokens and each decode step's) over the window's wall time.

After the window a sample of finished requests, drawn from the seed,
is run once through the float32 reference over its prompt and its
served tokens: the widest gap by which a served token's reference
logit lies below the reference's best is the number compared.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from .cell import Check, Outcome, RunContext, use_program
from .instrument import peak_memory_bytes
from .peaks import decode_step_cost, prefill_flops
from .traffic import jax_key_seed, prompts, sample_rows

WARMUP_BATCH = 2 ** 31        # prompts of the set-up batch: no window batch's

# the configuration file's keys, and the program's ArchConfig fields
# they must equal
_ARCH_FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
                "num_attention_heads": "n_heads",
                "num_key_value_heads": "n_kv_heads", "head_dim": "hd",
                "intermediate_size": "d_ff", "vocab_size": "vocab",
                "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
                "tie_word_embeddings": "tie_embeddings",
                "torch_dtype": "dtype"}


def program_model(c: dict):
    """The program's model for configuration ``c``: its registered
    architecture with the file's choice of tied embeddings (an option of
    the program), checked field by field against the file."""
    import dataclasses
    use_program()
    from repro.configs.registry import get_config
    from repro.models.model import make_model
    arch = dataclasses.replace(get_config(c["program_arch"]),
                               tie_embeddings=c["tie_word_embeddings"])
    for key, attr in _ARCH_FIELDS.items():
        if getattr(arch, attr) != c[key]:
            raise SystemExit(f"{c['program_arch']}: {attr}="
                             f"{getattr(arch, attr)!r} but the "
                             f"configuration file has {key}={c[key]!r}")
    if arch.mlp_act != "swiglu" or arch.rope != "rope" or arch.sliding_window:
        raise SystemExit(f"{c['program_arch']}: not a full-attention "
                         f"rotary SwiGLU decoder")
    return make_model(arch)


def _pad_to(full_specs):
    """Jitted: place a prefill's cache at the front of the full-length
    cache, leaf by leaf (the shapes differ only along the sequence)."""
    import jax
    import jax.numpy as jnp

    def pad(part):
        return jax.tree_util.tree_map(
            lambda p, f: jnp.pad(p, [(0, fs - ps) for ps, fs in
                                     zip(p.shape, f.shape)]).astype(f.dtype),
            part, full_specs)
    return jax.jit(pad)


def run(ctx: RunContext, fault=None, control: bool = False) -> Outcome:
    """``fault`` (tests only) wraps the compiled steps to break them.
    ``control`` also reads the control on the same requests: at each
    checked position the token the fp8 reference puts first, and its
    gap below the float32 reference's best (``bench/control.py``)."""
    import jax
    import jax.numpy as jnp
    from reference import dense_lm

    cell = ctx.cell
    c, t = cell.config, cell.traffic
    B, P, G = t["batch"], t["prompt_len"], t["gen_len"]
    model = program_model(c)
    from repro.models.config import ShapeConfig
    shape = ShapeConfig("serve", P + G, B, "decode")
    dev = ctx.devices[0]

    params = dense_lm.init_weights(c, jax_key_seed(ctx.seed),
                                   jnp.dtype(c["torch_dtype"]))
    cache_specs = model.cache_specs(shape)
    tok_spec = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
    prefill = jax.jit(model.prefill_step).lower(
        params, {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)}).compile()
    serve = jax.jit(model.serve_step, donate_argnums=(1,)).lower(
        params, cache_specs, tok_spec,
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    pcache_specs = jax.eval_shape(
        model.prefill_step, params,
        {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)})[1]
    pad = _pad_to(cache_specs).lower(pcache_specs).compile()

    def pick_tokens(logits):
        return jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    pick = jax.jit(pick_tokens).lower(
        jax.ShapeDtypeStruct((B, 1, c["vocab_size"]), jnp.float32)).compile()
    if fault is not None:
        prefill, serve = fault(prefill, serve)
    positions = [jax.device_put(np.int32(P + i), dev) for i in range(G - 1)]

    def serve_batch(b: int, t0: float = None, steps: int = G - 1):
        """One batch; returns (start time, served [B, n], token times).
        With ``t0`` it stops at the first step ending past the window."""
        toks = jax.device_put(prompts(t, c["vocab_size"], ctx.seed, b), dev)
        start = time.perf_counter()
        with ctx.spans.span("prefill"):
            logits, pcache = prefill(params, {"tokens": toks})
            tok = pick(logits)
            cache = pad(pcache)
            del pcache, logits
            out = [np.asarray(tok)]
        times = [time.perf_counter()]
        for i in range(steps):
            if t0 is not None and times[-1] - t0 >= ctx.seconds:
                break
            with ctx.spans.span("decode"):
                logits, cache = serve(params, cache, {"tokens": tok},
                                      positions[i])
                tok = pick(logits)
                out.append(np.asarray(tok))
            times.append(time.perf_counter())
        del cache
        return start, np.concatenate(out, axis=1), times

    # set-up: every program above runs once on the cell's shapes
    serve_batch(WARMUP_BATCH, steps=2)
    batches: List[Dict] = []
    with ctx.window() as t0:
        b = 0
        while ctx.elapsed(t0) < ctx.seconds:
            start, served, times = serve_batch(b, t0)
            batches.append({"b": b, "start": start, "served": served,
                            "times": times})
            b += 1
    memory = peak_memory_bytes(ctx.devices)

    tokens = sum(B * len(x["times"]) for x in batches)
    done = [x for x in batches if x["served"].shape[1] == G]
    steps = [P + i for x in batches for i in range(len(x["times"]) - 1)]
    flops = sum(prefill_flops(c, B, P) for _ in batches) + \
        sum(decode_step_cost(c, B, p)[0] for p in steps)
    ctx.extra.update(decode_positions=steps, model_flops=flops, batch=B)

    # -- check: sampled finished requests against the reference --------- #
    rows = sample_rows(len(done), B, t["check_requests"], ctx.seed)
    seqs, served = [], []
    for r in rows:
        x = done[r // B]
        prompt = prompts(t, c["vocab_size"], ctx.seed, x["b"])[r % B]
        seqs.append(np.concatenate([prompt, x["served"][r % B, :-1]]))
        served.append(x["served"][r % B])
    limit = cell.limits["served_logit_gap"]
    gap, failed = None, 0
    if rows:
        gaps = dense_lm.served_gaps(c, params, np.stack(seqs),
                                    np.stack(served), P)
        row_gap = gaps.max(axis=1)
        gap = float(row_gap.max())
        failed = int(np.sum(~(row_gap <= limit)))
        if control:
            ctl = dense_lm.served_gaps(c, params, np.stack(seqs),
                                       np.stack(served), P, control=True)
            ctx.extra["control_gap"] = float(ctl.max())
    lines = [f"batches in the window: {len(batches)} ({len(done)} whole); "
             f"tokens {tokens}; decode steps {len(steps)}",
             f"checked requests {rows}: widest served-token logit gap "
             f"{gap!r} (limit {limit})"]
    checks = {"served_logit_gap": Check(gap, limit)}
    metrics = {"tokens_per_s": tokens / ctx.window_s}
    return Outcome(metrics=metrics, attempted=len(done) * B, failed=failed,
                   checks=checks, memory_peak_bytes=memory, lines=lines)
