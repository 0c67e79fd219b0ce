"""Causal GQA flash attention — Pallas TPU kernel.

TPU adaptation of flash attention: the grid iterates
(batch, q-head, q-block) in parallel and kv-blocks sequentially
("arbitrary" semantics); the online-softmax running max/denominator and
the output accumulator live in VMEM scratch.  Block shapes are MXU
aligned (q/kv blocks 128, head_dim up to 128, multiples of 8x128 VREG
tiles).  GQA is handled in the index maps: q head h reads kv head
h // (h_total / kv_total), so no KV duplication is materialized.

Validated on CPU with ``interpret=True`` against ``ref.ref_attention``
(see tests/test_kernels.py); on TPU runtimes ``interpret=False``
compiles to real Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref,
                  *, scale: float, block_q: int, block_k: int,
                  seq_q: int, seq_kv: int, causal: bool, window: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                    # [bq, d]
    k = k_ref[0, 0].astype(jnp.float32)                    # [bk, d]
    v = v_ref[0, 0].astype(jnp.float32)                    # [bk, d]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # [bq, bk]

    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + (seq_kv - seq_q)
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask = kpos <= qpos
    if window:
        mask = jnp.logical_and(mask, kpos > qpos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                 # [bq, 1]
    l_prev = l_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)                              # [bq, bk]
    alpha = jnp.exp(m_prev - m_cur)                     # [bq, 1]
    l_cur = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    m_ref[...] = m_cur
    l_ref[...] = l_cur
    acc_ref[...] = acc

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[0, 0] = (acc / jnp.maximum(l_cur, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int = 0,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = True) -> jax.Array:
    """q: [b, h, sq, d]; k, v: [b, kvh, skv, d] -> [b, h, sq, d]."""
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    assert h % kvh == 0, "GQA requires h % kvh == 0"
    g = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0
    scale = 1.0 / np.sqrt(d)

    grid = (b, h, sq // block_q, skv // block_k)

    kern = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_q=sq, seq_kv=skv, causal=causal, window=window)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, hh, qq, kk: (bb, hh, qq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, qq, kk, g=g: (bb, hh // g, kk, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, qq, kk, g=g: (bb, hh // g, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, hh, qq, kk: (bb, hh, qq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m
            pltpu.VMEM((block_q, 1), jnp.float32),   # l
            pltpu.VMEM((block_q, d), jnp.float32),   # acc
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------- #
# flash-decode: single-token attention over a long KV cache
# ---------------------------------------------------------------------- #
def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref,
                   m_ref, l_ref, acc_ref,
                   *, scale: float, block_k: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # [1, d]
    k = k_ref[0, 0].astype(jnp.float32)                 # [bk, d]
    v = v_ref[0, 0].astype(jnp.float32)                 # [bk, d]
    length = len_ref[0]                                 # scalar s32

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # [1, bk]
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    s = jnp.where(kpos < length, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_cur = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_cur
    l_ref[...] = l_cur

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_cur, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_k", "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 lengths: jax.Array,
                 block_k: int = DEFAULT_BLOCK_K,
                 interpret: bool = True) -> jax.Array:
    """Single-token attention over a KV cache.

    q: [b, h, 1, d]; k, v: [b, kvh, S, d]; lengths: [b] (valid context
    per row, mask beyond).  Returns [b, h, 1, d].  The kv-block loop is
    the sequential grid dim with VMEM online-softmax scratch — the
    flash-decode pattern (on real TPU serving the cache is sequence-
    sharded and XLA combines the per-shard partial softmaxes).
    """
    b, h, _, d = q.shape
    kvh, S = k.shape[1], k.shape[2]
    g = h // kvh
    block_k = min(block_k, S)
    assert S % block_k == 0
    scale = 1.0 / np.sqrt(d)
    grid = (b, h, S // block_k)

    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda bb, hh, kk: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, kk, g=g: (bb, hh // g, kk, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, kk, g=g: (bb, hh // g, kk, 0)),
            pl.BlockSpec((1,), lambda bb, hh, kk: (bb,)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d),
                               lambda bb, hh, kk: (bb, hh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, lengths)
