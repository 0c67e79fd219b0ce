"""Mamba2 SSD intra-chunk kernel — Pallas TPU.

The SSD chunked algorithm has two parts:

1. **intra-chunk** (this kernel): per (batch, chunk, head), the masked
   quadratic form  y_intra = (L ∘ C Bᵀ)(dt·x)  plus the chunk state
   S = Bᵀ diag(decay)(dt·x) and the chunk's total decay — all
   MXU-friendly matmuls over a [Q, N]x[N, Q]->[Q, Q] tile held in VMEM;
2. **inter-chunk** (ops.py): an associative scan over the per-chunk
   (decay, state) pairs and one einsum to add  C·S_prev  — O(s/Q) work,
   left in XLA where it fuses with the surrounding layer.

Grid: (batch, n_chunks, heads) all parallel — chunk recurrence is
carried OUTSIDE the kernel, so the grid has no sequential dimension.
Block shapes: chunk Q (default 128/256) x head_dim P x state N are
padded by the caller to multiples of 8x128 VREG tiles where needed.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
                      y_ref, state_ref, decay_ref, *, chunk: int):
    """One (batch, chunk, head) tile.

    x: [Q, P]; dt: [Q]; a: [1] (this head's A); b, c: [Q, N].
    Outputs: y [Q, P]; state [N, P]; decay [1] (total chunk decay-log).
    """
    x = x_ref[0, :, 0, :].astype(jnp.float32)     # [Q, P]
    dt = dt_ref[0, :, 0, :].astype(jnp.float32)   # [Q, 1] (kept 2D)
    A = a_ref[0].astype(jnp.float32)              # scalar
    B = b_ref[0, :, 0, :].astype(jnp.float32)     # [Q, N]
    C = c_ref[0, :, 0, :].astype(jnp.float32)     # [Q, N]

    dA = dt * A                                   # [Q, 1], negative
    seg = jnp.cumsum(dA, axis=0)                  # [Q, 1]
    total = seg[-1:, :]                           # [1, 1]

    # L[i, j] = exp(seg_i - seg_j) for j <= i else 0
    rel = seg - seg.reshape(1, chunk)             # [Q, Q]
    causal = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1) <= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    L = jnp.where(causal, jnp.exp(rel), 0.0)

    scores = jax.lax.dot_general(                  # C Bᵀ -> [Q, Q]
        C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    ydt = x * dt                                   # [Q, P]
    y = jax.lax.dot_general(                       # (scores ∘ L) ydt
        scores * L, ydt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    decay_to_end = jnp.exp(total - seg)            # [Q, 1]
    state = jax.lax.dot_general(                   # Bᵀ diag(w) ydt -> [N, P]
        B * decay_to_end, ydt, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)
    state_ref[0, 0, 0] = state.astype(state_ref.dtype)
    decay_ref[0, 0, 0] = total[0, 0].astype(decay_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_pallas(x: jax.Array, dt: jax.Array, A: jax.Array,
                     B: jax.Array, C: jax.Array, chunk: int,
                     interpret: bool = True
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Intra-chunk SSD.  x: [b, s, H, P]; dt: [b, s, H]; A: [H];
    B, C: [b, s, G, N].  Returns (y_intra [b,s,H,P],
    states [b,nc,H,N,P], decay_log [b,nc,H])."""
    b, s, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    g = H // G
    nc = s // chunk
    grid = (b, nc, H)

    # layout: iterate chunks via index maps on the seq dim
    y, states, decay = pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda bb, cc, hh: (bb, cc, hh, 0)),
            pl.BlockSpec((1, chunk, 1, 1), lambda bb, cc, hh: (bb, cc, hh, 0)),
            pl.BlockSpec((1,), lambda bb, cc, hh: (hh,)),
            pl.BlockSpec((1, chunk, 1, N),
                         lambda bb, cc, hh, g=g: (bb, cc, hh // g, 0)),
            pl.BlockSpec((1, chunk, 1, N),
                         lambda bb, cc, hh, g=g: (bb, cc, hh // g, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda bb, cc, hh: (bb, cc, hh, 0)),
            pl.BlockSpec((1, 1, 1, N, P), lambda bb, cc, hh: (bb, cc, hh, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda bb, cc, hh: (bb, cc, hh)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, H, P), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, H, N, P), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, H), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel")),
        interpret=interpret,
    )(x.reshape(b, nc * chunk, H, P),
      dt.reshape(b, s, H, 1),
      A, B, C)
    return y, states, decay
