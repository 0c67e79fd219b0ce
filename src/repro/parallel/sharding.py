"""Logical-axis sharding rules (DP / FSDP / TP / SP / EP).

Arrays are annotated with *logical* axis names; a ``Rules`` table maps
logical names to physical mesh axes.  The default (baseline) scheme:

* ``batch``    -> ``('pod', 'data')``  — data parallelism across pods and
  the FSDP axis within a pod.
* ``seq``      -> ``'model'``          — context/sequence parallelism: the
  residual stream is sequence-sharded over the model axis, so per-layer
  compute is distributed 16x regardless of head-count divisibility
  (several assigned archs have 24/40/48 heads, which do NOT divide the
  16-way model axis — head-sharded TP is not universally applicable).
* params: ``fsdp`` -> ``'data'`` (weight-gather per layer, Zero-3 style),
  ``tp`` -> ``'model'`` (MLP hidden / expert / vocab dims), and
  ``fsdp2d`` -> ``('data', 'model')`` for weights whose only shardable
  dim is ``embed`` (attention projections with awkward head counts).
* ``kv_seq``   -> ``'model'``          — decode-time KV caches are
  sequence-sharded (flash-decode style partial softmax; XLA GSPMD
  generates the cross-shard max/sum combine).

Hillclimbing swaps rules per-arch via ``Rules.override``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the replication (vma) check."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


Physical = Union[None, str, Tuple[str, ...]]


DEFAULT_RULES: Dict[str, Physical] = {
    "batch": ("pod", "data"),
    "seq": "model",
    "kv_seq": "model",
    "embed": None,            # activation embed dim: replicated
    "heads": None,
    "kv_heads": None,
    "head_dim": None,
    "fsdp": "data",           # param dim sharded Zero-3 style
    "tp": "model",            # param dim sharded tensor-parallel
    "fsdp2d": ("data", "model"),
    "vocab": "model",
    "expert": "model",
    "expert_cap": ("pod", "data"),
    "ssm_heads": "model",
    "ssm_state": None,
    "layers": None,           # stacked-layer leading axis
    "window": None,
}


@dataclass(frozen=True)
class Rules:
    table: Dict[str, Physical] = field(default_factory=lambda: dict(DEFAULT_RULES))

    def override(self, **kv: Physical) -> "Rules":
        t = dict(self.table)
        t.update(kv)
        return Rules(t)

    def spec(self, *logical: Optional[str]) -> P:
        """Map logical axis names to a PartitionSpec."""
        phys = []
        used: set = set()
        for name in logical:
            if name is None:
                phys.append(None)
                continue
            p = self.table.get(name)
            # an axis may appear only once in a spec; drop duplicates
            if p is None:
                phys.append(None)
            elif isinstance(p, tuple):
                keep = tuple(a for a in p if a not in used)
                used.update(keep)
                phys.append(keep if keep else None)
            else:
                if p in used:
                    phys.append(None)
                else:
                    used.add(p)
                    phys.append(p)
        return P(*phys)

    def shard(self, mesh: Mesh, *logical: Optional[str]) -> NamedSharding:
        return NamedSharding(mesh, self.spec(*logical))


@dataclass(frozen=True)
class ShardingCtx:
    """Rules + (optional) mesh.  With ``mesh=None`` constraints are
    no-ops, so the same model code runs in single-device smoke tests and
    in the 512-chip dry-run."""

    rules: Rules = field(default_factory=Rules)
    mesh: Optional[Mesh] = None

    def spec(self, *logical: Optional[str]) -> P:
        s = self.rules.spec(*logical)
        if self.mesh is None:
            return s
        # drop axes not present in this mesh (e.g. 'pod' on single-pod)
        present = set(self.mesh.axis_names)

        def keep(p):
            if p is None:
                return None
            if isinstance(p, tuple):
                t = tuple(a for a in p if a in present)
                return t if t else None
            return p if p in present else None
        return P(*[keep(p) for p in s])

    def sharding(self, *logical: Optional[str]) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(*logical))

    def override(self, **kv: Physical) -> "ShardingCtx":
        return ShardingCtx(self.rules.override(**kv), self.mesh)


def constrain(x: jax.Array, ctx: ShardingCtx, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint via logical names (no-op without mesh)."""
    if ctx.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, ctx.sharding(*logical))


def divisible(n: int, mesh: Mesh, phys: Physical) -> bool:
    if phys is None:
        return True
    axes = (phys,) if isinstance(phys, str) else phys
    k = 1
    for a in axes:
        k *= dict(zip(mesh.axis_names, mesh.devices.shape)).get(a, 1)
    return n % k == 0
