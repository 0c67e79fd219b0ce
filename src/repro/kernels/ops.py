"""Jit'd dispatch wrappers for the Pallas kernels.

``use_pallas='auto'`` selects the compiled Pallas kernel on TPU backends
and the XLA reference path elsewhere; ``'pallas'`` forces the compiled
kernel (it fails off TPU); ``'interpret'`` runs the kernel body in
interpret mode (CPU validation); ``'xla'`` forces the oracle.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention
from .ref import ref_attention
from .ssd_scan import ssd_chunk_pallas


def _backend() -> str:
    return jax.default_backend()


def attention_op(q: jax.Array, k: jax.Array, v: jax.Array,
                 causal: bool = True, window: int = 0,
                 use_pallas: str = "auto") -> jax.Array:
    """q: [b, h, sq, d]; k, v: [b, kvh, skv, d]."""
    if use_pallas == "xla" or (use_pallas == "auto" and _backend() != "tpu"):
        return ref_attention(q, k, v, causal=causal, window=window)
    interpret = use_pallas == "interpret"
    return flash_attention(q, k, v, causal=causal, window=window,
                           interpret=interpret)


def ssd_scan_op(x: jax.Array, dt: jax.Array, A: jax.Array,
                B: jax.Array, C: jax.Array, chunk: int,
                initial_state: Optional[jax.Array] = None,
                return_state: bool = False,
                use_pallas: str = "auto"):
    """Full SSD scan: Pallas intra-chunk kernel + XLA inter-chunk
    associative scan.  Shapes as in ``ref.ref_ssd``."""
    if use_pallas == "xla" or (use_pallas == "auto" and _backend() != "tpu"):
        from ..models.mamba2 import ssd_chunked
        return ssd_chunked(x, dt, A, B, C, chunk,
                           initial_state=initial_state,
                           return_state=return_state)
    interpret = use_pallas == "interpret"
    b, s, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    nc = s // chunk
    rep = H // G

    y_intra, states, decay_log = ssd_chunk_pallas(
        x, dt, A, B, C, chunk, interpret=interpret)
    # states: [b, nc, H, N, P]; decay_log: [b, nc, H]
    chunk_decay = jnp.exp(decay_log)

    def combine(a, bb):
        da, sa = a
        db, sb = bb
        return (da * db, sa * db[..., None, None] + sb)

    dcum, scum = jax.lax.associative_scan(
        combine, (chunk_decay, states), axis=1)
    init = (jnp.zeros_like(states[:, :1]) if initial_state is None
            else initial_state.transpose(0, 1, 3, 2)[:, None]
            .astype(states.dtype))
    carried = scum[:, :-1] + init * dcum[:, :-1, :, None, None]
    prev = jnp.concatenate([init, carried], axis=1)     # [b,nc,H,N,P]

    # y_inter[j] = C_j exp(seg_j) S_prev — recompute seg cheaply in XLA
    dA = (dt.astype(jnp.float32)
          * A.astype(jnp.float32)[None, None, :]).reshape(b, nc, chunk, H)
    seg = jnp.cumsum(dA, axis=2)
    in_decay = jnp.exp(seg)                             # [b,nc,q,H]
    Cg = jnp.repeat(C.reshape(b, nc, chunk, G, N), rep, axis=3)
    y_inter = jnp.einsum("bcqhn,bchnp,bcqh->bcqhp",
                         Cg.astype(jnp.float32), prev, in_decay)
    y = y_intra.reshape(b, nc, chunk, H, P) + y_inter
    y = y.reshape(b, s, H, P).astype(x.dtype)
    if not return_state:
        return y
    final = prev[:, -1] * chunk_decay[:, -1, :, None, None] + states[:, -1]
    return y, final.transpose(0, 1, 3, 2)               # [b,H,P,N]
