"""Decoder-only backbone: init specs, forward, prefill and decode steps.

One code path covers all 10 assigned architectures:

* dense transformers (llama3.2 / phi3 / nemotron / phi4 / musicgen /
  qwen2-vl backbone) — scan over stacked layers; musicgen's layers also
  cross-attend to conditioning states, and it reads and predicts four
  codebooks;
* MoE (qwen3-moe every layer; llama4-maverick interleaved dense/MoE) —
  scan over stacked groups of ``moe_every`` layers;
* SSM (mamba2) — scan over stacked Mamba2 blocks;
* hybrid (zamba2) — scan over groups of Mamba2 blocks with one *shared*
  attention+MLP block applied between groups (parameters shared across
  all applications, Zamba2-style).

Layers are stacked on a leading axis and iterated with ``jax.lax.scan``
(+ optional ``jax.checkpoint`` for activation rematerialization), which
keeps compile time flat in depth (80-layer qwen2-vl compiles the same
program as 28-layer llama3.2).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.sharding import ShardingCtx, constrain
from .config import ArchConfig
from .layers import (attention, attn_specs, codebook_cross_entropy,
                     codebook_logits, codebook_specs, cond_specs,
                     cross_attention, cross_entropy, embed_codes,
                     embed_specs, embed_tokens, lm_logits, mlp, mlp_specs,
                     project_cond, stack_specs, xattn_specs)
from .mamba2 import mamba_layer, mamba_specs, mamba_state_specs
from .moe import moe, moe_specs


# ---------------------------------------------------------------------- #
# parameter specs
# ---------------------------------------------------------------------- #
def _group_layout(cfg: ArchConfig) -> Tuple[int, int]:
    """(n_groups, layers_per_group) for the scan."""
    if cfg.family == "hybrid" and cfg.shared_attn_every:
        per = cfg.shared_attn_every
        return cfg.n_layers // per, per
    if cfg.is_moe and cfg.moe_every > 1:
        return cfg.n_layers // cfg.moe_every, cfg.moe_every
    return cfg.n_layers, 1


def init_specs(cfg: ArchConfig) -> Dict[str, Any]:
    """The full parameter-spec tree for an architecture."""
    groups, per = _group_layout(cfg)
    specs: Dict[str, Any] = {"embed": codebook_specs(cfg) if cfg.is_codebook
                             else embed_specs(cfg)}
    if cfg.cond_dim:
        specs["cond"] = cond_specs(cfg)
    if cfg.family == "ssm":
        specs["blocks"] = stack_specs(mamba_specs(cfg), cfg.n_layers)
    elif cfg.family == "hybrid":
        specs["blocks"] = stack_specs(mamba_specs(cfg), cfg.n_layers)
        specs["shared"] = {"attn": attn_specs(cfg), "mlp": mlp_specs(cfg)}
    elif cfg.is_moe and cfg.moe_every > 1:
        # interleaved: each group = (dense layer, ..., final MoE layer)
        specs["blocks"] = stack_specs(
            {"dense": {"attn": attn_specs(cfg), "mlp": mlp_specs(cfg)},
             "moe": {"attn": attn_specs(cfg), "ffn": moe_specs(cfg)}},
            groups)
    elif cfg.is_moe:
        specs["blocks"] = stack_specs(
            {"attn": attn_specs(cfg), "ffn": moe_specs(cfg)}, cfg.n_layers)
    else:
        layer = {"attn": attn_specs(cfg), "mlp": mlp_specs(cfg)}
        if cfg.cond_dim:
            layer["xattn"] = xattn_specs(cfg)
        specs["blocks"] = stack_specs(layer, cfg.n_layers)
    return specs


# ---------------------------------------------------------------------- #
# position streams
# ---------------------------------------------------------------------- #
def make_positions(cfg: ArchConfig, batch: int, seq: int,
                   offset: int = 0) -> jax.Array:
    pos = jnp.arange(seq, dtype=jnp.int32)[None, :] + offset
    pos = jnp.broadcast_to(pos, (batch, seq))
    if cfg.rope == "mrope":
        return jnp.broadcast_to(pos[None], (3, batch, seq))  # t=h=w (text)
    return pos


def _sinusoid(positions: jax.Array, e: int, dtype) -> jax.Array:
    """MusicGen's absolute sinusoidal embedding, [b, s, e]: with
    h = e/2, [cos(t w_j) for j < h, sin(t w_j) for j < h] where
    w_j = 10000^(-j/(h-1)); added unscaled."""
    half = e // 2
    freqs = np.exp(-np.log(10000.0) * np.arange(half) / (half - 1))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1).astype(dtype)


# ---------------------------------------------------------------------- #
# forward (train / prefill)
# ---------------------------------------------------------------------- #
def forward(params: Dict, cfg: ArchConfig, ctx: ShardingCtx,
            tokens: Optional[jax.Array] = None,
            embeds: Optional[jax.Array] = None,
            want_cache: bool = False,
            logits_positions: str = "all",
            codes: Optional[jax.Array] = None,
            cond: Optional[jax.Array] = None,
            cond_mask: Optional[jax.Array] = None):
    """Full-sequence forward.  Returns (logits, cache-or-None).

    ``tokens`` [b, s] for token frontends; ``embeds`` [b, s, e] for the
    stubbed vision frontend (precomputed patch embeddings); ``codes``
    [b, K, s] for codebook models, whose logits are [b, K, s, vocab].
    Models with cross-attention take ``cond`` [b, t, cond_dim] and
    ``cond_mask`` [b, t] (True on the real positions).
    ``logits_positions='last'`` (prefill serving) projects only the final
    position through the LM head — at 32K context this removes the
    [b, s, vocab] logits buffer entirely (§Perf).
    """
    if codes is not None:
        b, _, s = codes.shape
        x = embed_codes(codes, params["embed"], cfg, ctx)
    elif embeds is not None:
        x = constrain(embeds.astype(jnp.dtype(cfg.dtype)), ctx,
                      "batch", "seq", "embed")
        b, s, _ = embeds.shape
    else:
        b, s = tokens.shape
        x = embed_tokens(tokens, params["embed"], cfg, ctx)
    positions = make_positions(cfg, b, s)
    if cfg.rope == "abs_sin":
        x = x + _sinusoid(positions, cfg.d_model, x.dtype)
    if cfg.cond_dim:
        cond_x = project_cond(cond, params["cond"], cfg, ctx)

    groups, per = _group_layout(cfg)
    collect = want_cache

    def attn_block(x, ap):
        a, kv = attention(x, ap, cfg, ctx, positions, want_cache=collect)
        x = constrain(x + a, ctx, "batch", "seq", "embed")
        return x, (kv if collect else ())

    def body(x, bp):
        kv_out = ()
        if cfg.family == "ssm":
            y, st = mamba_layer(x, bp, cfg, ctx, want_state=collect)
            x = constrain(x + y, ctx, "batch", "seq", "embed")
            kv_out = st if collect else ()
        elif cfg.family == "hybrid":
            # bp: [per, ...] stacked mamba sub-blocks for this group
            def inner(x, sub):
                y, st = mamba_layer(x, sub, cfg, ctx, want_state=collect)
                return (constrain(x + y, ctx, "batch", "seq", "embed"),
                        st if collect else ())
            x, states = jax.lax.scan(inner, x, bp)
            x, kv = attn_block(x, params["shared"]["attn"])
            x = x + mlp(x, params["shared"]["mlp"], cfg, ctx)
            x = constrain(x, ctx, "batch", "seq", "embed")
            kv_out = (states, kv) if collect else ()
        elif cfg.is_moe and cfg.moe_every > 1:
            x, kv1 = attn_block(x, bp["dense"]["attn"])
            x = x + mlp(x, bp["dense"]["mlp"], cfg, ctx)
            x, kv2 = attn_block(x, bp["moe"]["attn"])
            x = x + moe(x, bp["moe"]["ffn"], cfg, ctx)
            x = constrain(x, ctx, "batch", "seq", "embed")
            kv_out = (kv1, kv2) if collect else ()
        elif cfg.is_moe:
            x, kv_out = attn_block(x, bp["attn"])
            x = x + moe(x, bp["ffn"], cfg, ctx)
            x = constrain(x, ctx, "batch", "seq", "embed")
        else:
            x, kv_out = attn_block(x, bp["attn"])
            if cfg.cond_dim:
                x = x + cross_attention(x, bp["xattn"], cond_x, cond_mask,
                                        cfg, ctx)
                x = constrain(x, ctx, "batch", "seq", "embed")
            x = x + mlp(x, bp["mlp"], cfg, ctx)
            x = constrain(x, ctx, "batch", "seq", "embed")
        return x, kv_out

    blocks = params["blocks"]
    if cfg.cast_params_once:
        # §Perf: cast block params to the compute dtype BEFORE the scan,
        # so per-layer FSDP all-gathers move bf16 (half the f32 bytes).
        cdt = jnp.dtype(cfg.dtype)
        blocks = jax.tree_util.tree_map(
            lambda a: a.astype(cdt) if a.dtype == jnp.float32 else a, blocks)
    if cfg.family == "hybrid":
        # outer scan over groups; inner scan over the per-group SSM blocks
        blocks = jax.tree_util.tree_map(
            lambda a: a.reshape((groups, per) + a.shape[1:]), blocks)
    step = jax.checkpoint(body) if cfg.remat else body
    x, caches = jax.lax.scan(step, x, blocks)
    if logits_positions == "last":
        x = x[:, -1:, :]
    if cfg.is_codebook:
        logits = codebook_logits(x, params["embed"], cfg, ctx)
    else:
        logits = lm_logits(x, params["embed"], cfg, ctx)
    return logits, (_pack_cache(cfg, caches) if want_cache else None)


def _pack_cache(cfg: ArchConfig, caches) -> Dict[str, jax.Array]:
    """Convert scan-collected ys into the decode-cache dict layout."""
    if cfg.family == "ssm":
        return caches                                   # {"conv","ssm"} [L,...]
    if cfg.family == "hybrid":
        states, kv = caches                             # states [G, per, ...]
        groups, per = _group_layout(cfg)
        flat = jax.tree_util.tree_map(
            lambda a: a.reshape((groups * per,) + a.shape[2:]), states)
        return {"conv": flat["conv"], "ssm": flat["ssm"],
                "shared_k": kv["k"], "shared_v": kv["v"]}
    if cfg.is_moe and cfg.moe_every > 1:
        kv1, kv2 = caches
        return {"k": jnp.stack([kv1["k"], kv2["k"]], axis=1),
                "v": jnp.stack([kv1["v"], kv2["v"]], axis=1)}
    return {"k": caches["k"], "v": caches["v"]}


def loss_fn(params: Dict, cfg: ArchConfig, ctx: ShardingCtx,
            batch: Dict[str, jax.Array]) -> jax.Array:
    if cfg.is_codebook:
        logits, _ = forward(params, cfg, ctx, codes=batch["codes"],
                            cond=batch.get("cond"),
                            cond_mask=batch.get("cond_mask"))
        return codebook_cross_entropy(logits, batch["labels"], cfg.vocab)
    logits, _ = forward(params, cfg, ctx,
                        tokens=batch.get("tokens"),
                        embeds=batch.get("embeds"))
    return cross_entropy(logits, batch["labels"], onehot=cfg.onehot_ce)


# ---------------------------------------------------------------------- #
# decode
# ---------------------------------------------------------------------- #
def init_cache_specs(cfg: ArchConfig, batch: int, seq: int,
                     dtype=jnp.bfloat16) -> Dict[str, Any]:
    """ShapeDtypeStructs for the decode cache (K/V head-major:
    [layers, b, kvh, S, d])."""
    groups, per = _group_layout(cfg)
    h = jax.ShapeDtypeStruct
    kvd = (batch, cfg.n_kv_heads, seq, cfg.hd)
    if cfg.family == "ssm":
        st = mamba_state_specs(cfg, batch)
        return {k: h((cfg.n_layers,) + v.shape, v.dtype) for k, v in st.items()}
    if cfg.family == "hybrid":
        st = mamba_state_specs(cfg, batch)
        cache = {k: h((cfg.n_layers,) + v.shape, v.dtype) for k, v in st.items()}
        cache["shared_k"] = h((groups,) + kvd, dtype)
        cache["shared_v"] = h((groups,) + kvd, dtype)
        return cache
    if cfg.is_moe and cfg.moe_every > 1:
        return {"k": h((groups, 2) + kvd, dtype), "v": h((groups, 2) + kvd, dtype)}
    return {"k": h((cfg.n_layers,) + kvd, dtype),
            "v": h((cfg.n_layers,) + kvd, dtype)}


def cache_shardings(cfg: ArchConfig, ctx: ShardingCtx):
    """Shardings matching init_cache_specs (seq-sharded KV, replicated
    tiny SSM states except heads over model)."""
    if ctx.mesh is None:
        return None
    sh = ctx.sharding
    if cfg.family == "ssm":
        return {"conv": sh("layers", "batch", None, None),
                "ssm": sh("layers", "batch", "ssm_heads", None, None)}
    kv = sh("layers", "batch", "kv_heads", "kv_seq", "head_dim")
    if cfg.family == "hybrid":
        return {"conv": sh("layers", "batch", None, None),
                "ssm": sh("layers", "batch", "ssm_heads", None, None),
                "shared_k": kv, "shared_v": kv}
    if cfg.is_moe and cfg.moe_every > 1:
        kv2 = sh("layers", None, "batch", "kv_heads", "kv_seq", "head_dim")
        return {"k": kv2, "v": kv2}
    return {"k": kv, "v": kv}


def _layer(stack: jax.Array, i: jax.Array) -> jax.Array:
    """Layer ``i`` of a stacked cache, read in place inside the scan."""
    return jax.lax.dynamic_index_in_dim(stack, i, 0, keepdims=False)


def _write_rows(stack: jax.Array, rows: jax.Array, pos) -> jax.Array:
    """Write the step's rows [..., b, kvh, 1, d] into the stacked cache
    [..., b, kvh, S, d] at sequence index ``pos``: one update of the
    donated buffer, in place."""
    start = [0] * stack.ndim
    start[stack.ndim - 2] = pos
    return jax.lax.dynamic_update_slice(stack, rows.astype(stack.dtype),
                                        tuple(start))


def decode_step(params: Dict, cache: Dict, cfg: ArchConfig, ctx: ShardingCtx,
                tokens: Optional[jax.Array] = None,
                embeds: Optional[jax.Array] = None,
                pos: jax.Array = None):
    """One decode step.  tokens [b, 1] (or embeds [b, 1, e]); ``pos`` is
    the scalar write position (current context length).  Returns
    (logits [b, 1, v], new_cache).

    The stacked K/V caches never pass through the layer scan as
    ``xs``/``ys``: each layer reads its slice of the closed-over stack,
    the scan emits only the new rows, and the rows are written into the
    stack once after it, so a donated cache is updated in place."""
    if embeds is not None:
        x = embeds.astype(jnp.dtype(cfg.dtype))
        b = embeds.shape[0]
    else:
        b = tokens.shape[0]
        x = embed_tokens(tokens, params["embed"], cfg, ctx)
    positions = make_positions(cfg, b, 1, offset=0) + pos
    if cfg.rope == "abs_sin":
        x = x + _sinusoid(positions, cfg.d_model, x.dtype)

    groups, per = _group_layout(cfg)

    if cfg.family == "ssm":
        def body(x, sc):
            bp, st = sc
            y, new_st = mamba_layer(x, bp, cfg, ctx, state=st)
            return x + y, new_st
        x, new_states = jax.lax.scan(
            body, x, (params["blocks"], {"conv": cache["conv"],
                                         "ssm": cache["ssm"]}))
        logits = lm_logits(x, params["embed"], cfg, ctx)
        return logits, new_states

    def attend(x, ap, i, ks, vs):
        a, row = attention(x, ap, cfg, ctx, positions,
                           cache={"k": _layer(ks, i), "v": _layer(vs, i)})
        return x + a, row

    if cfg.family == "hybrid":
        mam = jax.tree_util.tree_map(
            lambda a: a.reshape((groups, per) + a.shape[1:]),
            {"conv": cache["conv"], "ssm": cache["ssm"]})
        blocks = jax.tree_util.tree_map(
            lambda a: a.reshape((groups, per) + a.shape[1:]), params["blocks"])

        def body(x, sc):
            bp, st, i = sc
            def inner(x, sub):
                subp, subst = sub
                y, nst = mamba_layer(x, subp, cfg, ctx, state=subst)
                return x + y, nst
            x, new_st = jax.lax.scan(inner, x, (bp, st))
            x, row = attend(x, params["shared"]["attn"], i,
                            cache["shared_k"], cache["shared_v"])
            x = x + mlp(x, params["shared"]["mlp"], cfg, ctx)
            return x, (new_st, row)
        x, (new_st, rows) = jax.lax.scan(body, x, (blocks, mam,
                                                   jnp.arange(groups)))
        flat = jax.tree_util.tree_map(
            lambda a: a.reshape((groups * per,) + a.shape[2:]), new_st)
        logits = lm_logits(x, params["embed"], cfg, ctx)
        return logits, {"conv": flat["conv"], "ssm": flat["ssm"],
                        "shared_k": _write_rows(cache["shared_k"],
                                                rows["k"], pos),
                        "shared_v": _write_rows(cache["shared_v"],
                                                rows["v"], pos)}

    if cfg.is_moe and cfg.moe_every > 1:
        def body(x, sc):
            bp, i = sc
            ks, vs = _layer(cache["k"], i), _layer(cache["v"], i)
            x, row1 = attend(x, bp["dense"]["attn"], 0, ks, vs)
            x = x + mlp(x, bp["dense"]["mlp"], cfg, ctx)
            x, row2 = attend(x, bp["moe"]["attn"], 1, ks, vs)
            x = x + moe(x, bp["moe"]["ffn"], cfg, ctx)
            return x, jax.tree_util.tree_map(
                lambda r1, r2: jnp.stack([r1, r2]), row1, row2)
        x, rows = jax.lax.scan(body, x, (params["blocks"],
                                         jnp.arange(groups)))
    else:
        def body(x, sc):
            bp, i = sc
            x, row = attend(x, bp["attn"], i, cache["k"], cache["v"])
            ffn = moe(x, bp["ffn"], cfg, ctx) if cfg.is_moe \
                else mlp(x, bp["mlp"], cfg, ctx)
            return x + ffn, row
        x, rows = jax.lax.scan(body, x, (params["blocks"],
                                         jnp.arange(cfg.n_layers)))
    logits = lm_logits(x, params["embed"], cfg, ctx)
    return logits, {"k": _write_rows(cache["k"], rows["k"], pos),
                    "v": _write_rows(cache["v"], rows["v"], pos)}
