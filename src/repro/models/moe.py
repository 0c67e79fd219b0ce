"""Mixture-of-Experts layer (token-choice top-k, capacity-based dispatch).

TPU-native adaptation: instead of ragged all-to-all (the GPU idiom), we
use the GShard/Switch *capacity* formulation with a sort-free rank
computation and static-shape scatter/gather:

1. route: top-k experts per token, gates renormalized over the top-k;
2. rank each (token, k) pair within its expert via argsort;
3. scatter tokens into a dispatch buffer [E, C, d] (overflow dropped),
   sharded expert->'model' and capacity->('pod','data') so XLA GSPMD
   materializes the dispatch as an all-to-all over the model axis;
4. batched expert matmuls with stacked expert weights [E, d, f];
5. gather back and combine with gates.

`moe_impl='dense'` computes every expert for every token and does a
weighted combine — simple and collective-free; used as the oracle in
tests and as a fallback for tiny smoke configs.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


from ..parallel.sharding import (ShardingCtx, constrain,
                                shard_map_unchecked as _shard_map)
from .config import ArchConfig
from .layers import ParamSpec, rmsnorm


def moe_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    e, f, E = cfg.d_model, cfg.expert_ff, cfg.n_experts
    specs = {
        "router": ParamSpec((e, E), (None, None), init="small"),
        "w_up": ParamSpec((E, e, f), ("expert", "fsdp", None)),
        "w_gate": ParamSpec((E, e, f), ("expert", "fsdp", None)),
        "w_down": ParamSpec((E, f, e), ("expert", None, "fsdp")),
        "norm": ParamSpec((e,), (None,), init="zeros"),
    }
    if cfg.moe_shared:
        specs["shared_up"] = ParamSpec((e, f * cfg.moe_shared), ("fsdp", "tp"))
        specs["shared_gate"] = ParamSpec((e, f * cfg.moe_shared), ("fsdp", "tp"))
        specs["shared_down"] = ParamSpec((f * cfg.moe_shared, e), ("tp", "fsdp"))
    return specs


def _expert_ffn(xb: jax.Array, p: Dict, cfg: ArchConfig) -> jax.Array:
    """xb: [E, C, e] -> [E, C, e] via per-expert SwiGLU/act."""
    cdt = xb.dtype
    up = jnp.einsum("ecd,edf->ecf", xb, p["w_up"].astype(cdt))
    if cfg.mlp_act == "swiglu":
        gate = jnp.einsum("ecd,edf->ecf", xb, p["w_gate"].astype(cdt))
        h = jax.nn.silu(gate) * up
    elif cfg.mlp_act == "relu2":
        r = jax.nn.relu(up)
        h = r * r
    else:
        h = jax.nn.gelu(up)
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(cdt))


def _route(xn: jax.Array, p: Dict, cfg: ArchConfig):
    """-> gates [T, k] fp32 (renormalized), ids [T, k] int32."""
    logits = (xn.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, ids


def moe_dense(x: jax.Array, p: Dict, cfg: ArchConfig, ctx: ShardingCtx) -> jax.Array:
    """Oracle path: every expert computed for every token."""
    b, s, e = x.shape
    cdt = x.dtype
    xn = rmsnorm(x, p["norm"], cfg.norm_eps)
    flat = xn.reshape(b * s, e)
    gates, ids = _route(flat, p, cfg)
    # [E, T, e] -> expert outputs for all tokens
    ally = _expert_ffn(jnp.broadcast_to(flat[None], (cfg.n_experts, b * s, e)),
                       p, cfg)                                  # [E, T, e]
    onehot = jax.nn.one_hot(ids, cfg.n_experts, dtype=jnp.float32)   # [T,k,E]
    weights = jnp.einsum("tk,tke->te", gates, onehot)                # [T,E]
    y = jnp.einsum("te,etd->td", weights.astype(cdt), ally)
    y = y + _shared(flat, p, cfg)
    return y.reshape(b, s, e)


def moe_dispatch(x: jax.Array, p: Dict, cfg: ArchConfig, ctx: ShardingCtx) -> jax.Array:
    """Capacity-based scatter dispatch (see module docstring)."""
    b, s, e = x.shape
    cdt = x.dtype
    E, k = cfg.n_experts, cfg.top_k
    T = b * s
    C = max(int(T * k * cfg.capacity_factor / E), 1)
    # round capacity so the ('pod','data') sharding of the buffer divides
    C = -(-C // 64) * 64 if T >= 4096 else C

    xn = rmsnorm(x, p["norm"], cfg.norm_eps).reshape(T, e)
    gates, ids = _route(xn, p, cfg)                      # [T,k]

    fid = ids.reshape(T * k)                             # flat expert ids
    fgate = gates.reshape(T * k)
    # rank of each (token,k) within its expert, via argsort
    order = jnp.argsort(fid, stable=True)
    sorted_fid = fid[order]
    # index of first occurrence of each expert in the sorted stream
    first = jnp.searchsorted(sorted_fid, sorted_fid, side="left")
    ranks_sorted = jnp.arange(T * k, dtype=jnp.int32) - first.astype(jnp.int32)
    inv = jnp.argsort(order, stable=True)
    rank = ranks_sorted[inv]                             # [T*k]

    keep = rank < C
    dest = jnp.where(keep, fid * C + rank, E * C)        # E*C = overflow slot
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)

    # scatter tokens into the dispatch buffer (+1 dump row for drops)
    buf = jnp.zeros((E * C + 1, e), cdt).at[dest].add(
        xn[tok] * keep[:, None].astype(cdt), mode="drop",
        indices_are_sorted=False, unique_indices=False)
    xb = buf[: E * C].reshape(E, C, e)
    xb = constrain(xb, ctx, "expert", "expert_cap", "embed")

    yb = _expert_ffn(xb, p, cfg)                         # [E, C, e]
    yb = constrain(yb, ctx, "expert", "expert_cap", "embed")

    flat_y = yb.reshape(E * C, e)
    gathered = jnp.take(flat_y, jnp.clip(dest, 0, E * C - 1), axis=0)
    gathered = gathered * (fgate * keep).astype(cdt)[:, None]
    y = jnp.zeros((T, e), cdt).at[tok].add(gathered)
    y = y + _shared(xn, p, cfg)
    y = y.reshape(b, s, e)
    return constrain(y, ctx, "batch", "seq", "embed")


def _shared(xn_flat: jax.Array, p: Dict, cfg: ArchConfig) -> jax.Array:
    if not cfg.moe_shared:
        return jnp.zeros_like(xn_flat)
    cdt = xn_flat.dtype
    up = xn_flat @ p["shared_up"].astype(cdt)
    gate = xn_flat @ p["shared_gate"].astype(cdt)
    return (jax.nn.silu(gate) * up) @ p["shared_down"].astype(cdt)


def moe_a2a(x: jax.Array, p: Dict, cfg: ArchConfig, ctx: ShardingCtx) -> jax.Array:
    """Expert parallelism via explicit all-to-all (shard_map).

    The GSPMD scatter path (``moe_dispatch``) materializes the global
    [E, C, d] buffer per device and all-reduces it — catastrophic at 128
    experts.  Here each model shard owns E/n_model experts and tokens
    move with two all-to-alls (out and back), the TPU-native MoE
    pattern:

      1. route locally; target shard = expert // experts_per_shard;
      2. pack (token, k) pairs into a [n_shards, S_cap, d] send buffer
         (capacity-dropped, rank via argsort);
      3. ``jax.lax.all_to_all`` over 'model';
      4. local capacity dispatch to the shard's own experts, batched
         expert FFN, combine;
      5. all-to-all back and weighted scatter-add into the tokens.

    Per-device collective bytes/layer = 2 x (T_loc * k * d), ~independent
    of E — vs the scatter path's O(E*C*d / n_dev) all-reduce.
    """
    mesh = ctx.mesh
    b, s, e = x.shape
    if mesh is None or "model" not in mesh.axis_names:
        return moe_dispatch(x, p, cfg, ctx)
    n_sh = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    if s % n_sh != 0 or cfg.n_experts % n_sh != 0:
        # decode (s=1) and odd expert counts: the token set per device
        # is tiny, the GSPMD scatter path is fine there
        return moe_dispatch(x, p, cfg, ctx)
    cdt = x.dtype
    E, k = cfg.n_experts, cfg.top_k
    e_loc = E // n_sh

    P_ = ctx.spec  # logical -> PartitionSpec helper
    x_spec = P_("batch", "seq", "embed")
    # expert weights: sharded over 'model' on the expert dim; the fsdp
    # dim is gathered on entry to the shard_map region (Zero-3 gather)
    w_spec = ctx.rules.spec("expert", None, None)
    r_spec = ctx.rules.spec(None, None)
    n_spec = ctx.rules.spec(None)

    def local_moe(xl, router, w_up, w_gate, w_down, norm):
        bl, sl, _ = xl.shape
        T = bl * sl
        xn = rmsnorm(xl, norm, cfg.norm_eps).reshape(T, e)
        logits = xn.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(probs, k)                # [T,k]
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

        S_cap = max(int(T * k * cfg.capacity_factor / n_sh), 8)
        fid = ids.reshape(T * k)
        dest = fid // e_loc                                 # target shard
        # rank within destination shard
        order = jnp.argsort(dest, stable=True)
        sorted_dest = dest[order]
        first = jnp.searchsorted(sorted_dest, sorted_dest, side="left")
        ranks_sorted = jnp.arange(T * k, dtype=jnp.int32) - first.astype(jnp.int32)
        rank = ranks_sorted[jnp.argsort(order, stable=True)]
        keep = rank < S_cap
        slot = jnp.where(keep, dest * S_cap + rank, n_sh * S_cap)

        tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        send_x = jnp.zeros((n_sh * S_cap + 1, e), cdt).at[slot].add(
            xn[tok] * keep[:, None].astype(cdt), mode="drop")[:-1]
        send_eid = jnp.full((n_sh * S_cap + 1,), -1, jnp.int32).at[slot].set(
            jnp.where(keep, fid % e_loc, -1), mode="drop")[:-1]

        recv_x = jax.lax.all_to_all(
            send_x.reshape(n_sh, S_cap, e), "model", 0, 0, tiled=False)
        recv_eid = jax.lax.all_to_all(
            send_eid.reshape(n_sh, S_cap), "model", 0, 0, tiled=False)
        # local expert dispatch over the shard's e_loc experts
        N = n_sh * S_cap
        rx = recv_x.reshape(N, e)
        rid = recv_eid.reshape(N)
        C2 = max(int(N * cfg.capacity_factor / e_loc), 8)
        order2 = jnp.argsort(rid, stable=True)
        sid = rid[order2]
        first2 = jnp.searchsorted(sid, sid, side="left")
        rk2 = (jnp.arange(N, dtype=jnp.int32)
               - first2.astype(jnp.int32))[jnp.argsort(order2, stable=True)]
        ok2 = jnp.logical_and(rid >= 0, rk2 < C2)
        slot2 = jnp.where(ok2, rid * C2 + rk2, e_loc * C2)
        buf = jnp.zeros((e_loc * C2 + 1, e), cdt).at[slot2].add(
            rx * ok2[:, None].astype(cdt), mode="drop")[:-1]
        xb = buf.reshape(e_loc, C2, e)

        if cfg.moe_ep2d and "data" in mesh.axis_names:
            # §Perf ep2d: expert weights stay f-sliced over 'data'; the
            # token buffers gather across 'data' into the expert matmul
            # and the f-partial outputs reduce-scatter back.  Trades the
            # 3x e x f weight gather for a 2x token-buffer exchange.
            xb = jax.lax.all_gather(xb, "data", axis=1,
                                    tiled=True)          # [e_loc, D*C2, e]
        up = jnp.einsum("ecd,edf->ecf", xb, w_up.astype(cdt))
        if cfg.mlp_act == "swiglu":
            gate = jnp.einsum("ecd,edf->ecf", xb, w_gate.astype(cdt))
            h = jax.nn.silu(gate) * up
        elif cfg.mlp_act == "relu2":
            r = jax.nn.relu(up)
            h = r * r
        else:
            h = jax.nn.gelu(up)
        yb = jnp.einsum("ecf,efd->ecd", h, w_down.astype(cdt))
        if cfg.moe_ep2d and "data" in mesh.axis_names:
            yb = jax.lax.psum_scatter(yb, "data", scatter_dimension=1,
                                      tiled=True)        # [e_loc, C2, e]

        ry = jnp.take(yb.reshape(e_loc * C2, e),
                      jnp.clip(slot2, 0, e_loc * C2 - 1), axis=0)
        ry = ry * ok2[:, None].astype(cdt)
        back = jax.lax.all_to_all(
            ry.reshape(n_sh, S_cap, e), "model", 0, 0, tiled=False)
        flat_back = back.reshape(n_sh * S_cap, e)
        got = jnp.take(flat_back, jnp.clip(slot, 0, n_sh * S_cap - 1), axis=0)
        fgate = gates.reshape(T * k).astype(cdt)
        got = got * (keep.astype(cdt) * fgate)[:, None]
        y = jnp.zeros((T, e), cdt).at[tok].add(got)
        return y.reshape(bl, sl, e)

    if cfg.moe_ep2d and "data" in mesh.axis_names:
        wu_spec = ctx.rules.spec("expert", None, "fsdp")   # f over 'data'
        wd_spec = ctx.rules.spec("expert", "fsdp", None)
    else:
        wu_spec = w_spec
        wd_spec = ctx.rules.spec("expert", None, None)
    y = _shard_map(
        local_moe, mesh=mesh,
        in_specs=(x_spec, r_spec,
                  wu_spec, wu_spec,
                  wd_spec, n_spec),
        out_specs=x_spec,
    )(x, p["router"], p["w_up"], p["w_gate"], p["w_down"], p["norm"])
    if cfg.moe_shared:
        # stay 3-D: reshaping [b->data, s->model, e] to [(b s), e] merges
        # two sharded dims and forces a full-sequence all-gather
        xn = rmsnorm(x, p["norm"], cfg.norm_eps)
        xn = constrain(xn, ctx, "batch", "seq", "embed")
        y = y + _shared(xn, p, cfg)
    return constrain(y, ctx, "batch", "seq", "embed")


def moe(x: jax.Array, p: Dict, cfg: ArchConfig, ctx: ShardingCtx) -> jax.Array:
    if cfg.moe_impl == "dense":
        return moe_dense(x, p, cfg, ctx)
    if cfg.moe_impl == "a2a":
        return moe_a2a(x, p, cfg, ctx)
    return moe_dispatch(x, p, cfg, ctx)
