"""Batched serving driver: prefill + decode with a KV cache.

Replica placement goes through the scheduler (a serving replica is just
another allocation; KubeFlux-style orchestration — see
benchmarks/kubeflux.py).  The data plane runs prefill once and then
streams decode steps, reusing the cache buffers (donated).

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
      --batch 4 --prompt-len 16 --gen 16          # reduced config
  PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b \
      --full --batch 4 --prompt-len 512 --gen 32  # registered widths
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.registry import ARCH_IDS, get_config
from ..models.config import ShapeConfig
from ..models.model import make_model
from .compile_cache import enable_compile_cache


def run_serving(arch: str, batch: int = 4, prompt_len: int = 16,
                gen: int = 16, smoke: bool = True, seed: int = 0) -> dict:
    """Greedy generation of ``gen`` tokens after a ``prompt_len`` prompt.

    ``smoke=True`` serves the reduced config; ``smoke=False`` the
    registered widths and depth.  Weights are random from ``seed`` and
    drawn directly in ``cfg.dtype``.  Returns the generated tokens, the
    logits behind each of them (``[batch, gen, vocab]``), the prompt,
    the model and its parameters (so a caller can check the logits
    against a full forward pass), and the compile, prefill and decode
    seconds."""
    cfg = get_config(arch)
    if smoke:
        cfg = cfg.reduced()
    max_len = prompt_len + gen
    shape = ShapeConfig("serve", max_len, batch, "decode")
    model = make_model(cfg)
    params = jax.jit(functools.partial(model.init_params, dtype=cfg.dtype))(
        jax.random.key(seed))

    rng = np.random.default_rng(seed)
    stub = cfg.frontend != "token"

    def step_input(tok):
        if stub:
            return {"embeds": jnp.asarray(rng.standard_normal(
                (batch, tok.shape[1], cfg.d_model)), jnp.float32)}
        return {"tokens": tok}

    prompt = step_input(jnp.asarray(rng.integers(
        0, cfg.vocab, (batch, prompt_len)), jnp.int32))
    cache = model.init_cache(shape)
    tok0 = jnp.zeros((batch, 1), jnp.int32)

    t0 = time.perf_counter()
    prefill = jax.jit(model.prefill_step).lower(params, prompt).compile()
    serve = jax.jit(model.serve_step, donate_argnums=(1,)).lower(
        params, cache, step_input(tok0), jnp.int32(0)).compile()
    compile_s = time.perf_counter() - t0

    # ---- prefill, placed into the max_len cache ----
    t0 = time.perf_counter()
    logits, pcache = prefill(params, prompt)

    def splice(full, part):
        # KV caches differ on the seq axis (written from 0); SSM states
        # match exactly
        if part.shape == full.shape:
            return part
        return jax.lax.dynamic_update_slice(
            full, part.astype(full.dtype), (0,) * full.ndim)
    cache = jax.tree_util.tree_map(splice, cache, pcache)
    jax.block_until_ready(cache)
    prefill_s = time.perf_counter() - t0

    # ---- greedy decode loop ----
    tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    out_logits = [logits]
    out_tokens = [np.asarray(tok)]
    t0 = time.perf_counter()
    for i in range(gen - 1):
        logits, cache = serve(params, cache, step_input(tok),
                              jnp.int32(prompt_len + i))
        tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
        out_logits.append(logits)
        out_tokens.append(np.asarray(tok))
    jax.block_until_ready(tok)
    decode_s = time.perf_counter() - t0
    toks = np.concatenate(out_tokens, axis=1)
    tps = batch * (gen - 1) / max(decode_s, 1e-9)
    print(f"compile {compile_s:.1f}s; prefill({batch}x{prompt_len}) "
          f"{prefill_s*1e3:.1f}ms; decode {gen-1} steps "
          f"{decode_s*1e3:.1f}ms ({tps:.0f} tok/s); "
          f"sample row: {toks[0][:8]}", flush=True)
    return {"tokens": toks, "logits": jnp.concatenate(out_logits, axis=1),
            "prompt": prompt, "model": model, "params": params,
            "compile_s": compile_s, "prefill_s": prefill_s,
            "decode_s": decode_s}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b", choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--full", action="store_true",
                    help="serve the registered widths and depth instead "
                         "of the reduced config")
    args = ap.parse_args()
    enable_compile_cache()
    run_serving(args.arch, batch=args.batch, prompt_len=args.prompt_len,
                gen=args.gen, smoke=not args.full)


if __name__ == "__main__":
    main()
