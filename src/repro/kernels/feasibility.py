"""Batched feasibility scan: a Pallas TPU kernel.

The device twin of ``core/flatgraph.batched_candidate_mask``, on the
same operands: one pass over the ``agg[vertex, type]`` pruning table
for a whole request matrix, producing the ``[N, V]`` root-feasibility
mask the batched backfill prefilter consumes.
``FlatGraph.feasible_roots_batch`` runs it when
``flatgraph.on_device()`` holds (a TPU backend).

Layout notes (TPU tiling wants the lane dim = 128):

* vertex columns ride the lane dimension as ``[1, V]`` rows and the
  aggregate table is transposed to ``[T, V]``, so the per-type
  comparisons are rank-2 broadcasts (``[BN, 1]`` against ``[1, BV]``);
* the nested-type check is a static unroll over T (a handful of
  resource types), each iteration one VPU compare+and;
* 62-bit property masks are split into two nonneg int31 halves — TPUs
  have no practical int64 lane support (and jax defaults to x32).

Grid is (N/BN, V/BV), both parallel; :func:`pack_inputs` pads N, V and
T and the caller slices the result.  :func:`run_packed` compiles the
kernel for a TPU backend and interprets it on any other (the CPU tests
that force the device half).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BN, _BV = 8, 128           # request x vertex block (8x128 VREG tile)
_LO31 = (1 << 31) - 1


def _backend() -> str:
    return jax.default_backend()


def _split_mask(mask: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """int64 property masks (<= 62 bits used) -> two nonneg int32."""
    m = np.asarray(mask, np.int64)
    return (m & _LO31).astype(np.int32), (m >> 31).astype(np.int32)


def _pad(a: np.ndarray, axis: int, mult: int, fill=0) -> np.ndarray:
    ext = (-a.shape[axis]) % mult
    if ext == 0:
        return a
    width = [(0, 0)] * a.ndim
    width[axis] = (0, ext)
    return np.pad(a, width, constant_values=fill)


# ---------------------------------------------------------------------- #
# Pallas kernel
# ---------------------------------------------------------------------- #
def _feasible_kernel(tid_ref, msize_ref, rmlo_ref, rmhi_ref, need_ref,
                     vtype_ref, vok_ref, vsize_ref, vmlo_ref, vmhi_ref,
                     agg_ref, out_ref, *, n_types: int):
    """One [BN, BV] tile: request columns [BN, 1] against vertex rows
    [1, BV]; the aggregate check unrolls statically over the types."""
    tid = tid_ref[...]              # [BN, 1]
    rmlo = rmlo_ref[...]
    rmhi = rmhi_ref[...]
    m = (vtype_ref[...] == tid) & (vok_ref[...] != 0)
    m &= vsize_ref[...] >= msize_ref[...]
    m &= (vmlo_ref[...] & rmlo) == rmlo
    m &= (vmhi_ref[...] & rmhi) == rmhi
    for t in range(n_types):        # static unroll: T is small
        m &= agg_ref[t:t + 1, :] >= need_ref[:, t:t + 1]
    out_ref[...] = m.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _feasible_pallas(tid, msize, rmlo, rmhi, need,
                     vtype, vok, vsize, vmlo, vmhi, agg_t,
                     interpret: bool = True):
    """tid/msize/rm*: [Np, 1]; need: [Np, Tp]; vtype/vok/vsize/vm*:
    [1, Vp]; agg_t: [Tp, Vp] (transposed).  All padded to block
    multiples by the caller.  Returns [Np, Vp] int32."""
    n_p, t_p = need.shape
    v_p = vtype.shape[1]
    grid = (n_p // _BN, v_p // _BV)
    rspec = pl.BlockSpec((_BN, 1), lambda i, j: (i, 0))
    nspec = pl.BlockSpec((_BN, t_p), lambda i, j: (i, 0))
    vspec = pl.BlockSpec((1, _BV), lambda i, j: (0, j))
    aspec = pl.BlockSpec((t_p, _BV), lambda i, j: (0, j))
    return pl.pallas_call(
        functools.partial(_feasible_kernel, n_types=t_p),
        grid=grid,
        in_specs=[rspec, rspec, rspec, rspec, nspec,
                  vspec, vspec, vspec, vspec, vspec, aspec],
        out_specs=pl.BlockSpec((_BN, _BV), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_p, v_p), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(tid, msize, rmlo, rmhi, need,
      vtype, vok, vsize, vmlo, vmhi, agg_t)


# ---------------------------------------------------------------------- #
# host half (pack) and device half (run)
# ---------------------------------------------------------------------- #
def pack_inputs(vtype: np.ndarray, vok: np.ndarray, vsize: np.ndarray,
                vmask: np.ndarray, agg: np.ndarray,
                tid: np.ndarray, msize: np.ndarray, rmask: np.ndarray,
                need: np.ndarray) -> tuple:
    """The kernel's arguments: cast to int32, property masks split, and
    padded to its blocks.  ``vmask``/``rmask`` are the int64 property
    bitmasks; ``agg`` is [V, T]; ``need`` is [N, T].  Their ``nbytes``
    are what the call copies to the device."""
    vmlo, vmhi = _split_mask(vmask)
    rmlo, rmhi = _split_mask(rmask)
    i32 = lambda a: np.asarray(a, np.int32)                     # noqa: E731
    # pad request rows, vertex lanes, and the type sublane; padded
    # vertices carry vok=0 (never feasible) and padded types need=0
    # against agg=0 (vacuously satisfied)
    rcol = lambda a: _pad(i32(a).reshape(-1, 1), 0, _BN)        # noqa: E731
    vrow = lambda a: _pad(i32(a).reshape(1, -1), 1, _BV)        # noqa: E731
    return (rcol(tid), rcol(msize), rcol(rmlo), rcol(rmhi),
            _pad(_pad(i32(need), 0, _BN), 1, 8),
            vrow(vtype), vrow(vok), vrow(vsize), vrow(vmlo), vrow(vmhi),
            _pad(_pad(i32(agg).T, 0, 8), 1, _BV))


def run_packed(args: tuple) -> np.ndarray:
    """Run the kernel on :func:`pack_inputs`' arguments and pull the
    whole padded int32 mask back to the host (rows are requests,
    columns vertices)."""
    return np.asarray(_feasible_pallas(*args,
                                       interpret=_backend() != "tpu"))
