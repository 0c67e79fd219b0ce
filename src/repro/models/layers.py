"""Model building blocks: norms, rotary embeddings, GQA attention,
cross-attention, MLPs, token and codebook embeddings and heads.

Pure-functional JAX.  Every layer takes a ``ShardingCtx`` so activation
sharding constraints are expressed with logical axis names (see
``repro.parallel.sharding``); with ``mesh=None`` they are no-ops and the
same code runs in CPU smoke tests.

Attention uses the XLA einsum path.  The Pallas kernels in
``repro.kernels`` (flash attention, SSD scan) are not on the model path;
they are validated separately in interpret mode.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.sharding import ShardingCtx, constrain
from .config import ArchConfig


# ---------------------------------------------------------------------- #
# param specs
# ---------------------------------------------------------------------- #
@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]      # logical axis names, len == ndim
    init: str = "normal"                 # normal | zeros | ones | small
    dtype: str = "float32"

    def materialize(self, key: jax.Array,
                    dtype: Optional[str] = None) -> jax.Array:
        """Draw the parameter directly in ``dtype`` (default: the
        spec's own), so no wider copy of it is ever built."""
        dt = jnp.dtype(dtype or self.dtype)
        if self.init == "zeros":
            return jnp.zeros(self.shape, dt)
        if self.init == "ones":
            return jnp.ones(self.shape, dt)
        fan_in = self.shape[-2] if len(self.shape) >= 2 else self.shape[-1]
        scale = 1.0 / np.sqrt(max(fan_in, 1))
        if self.init == "small":
            scale *= 0.1
        return jax.random.normal(key, self.shape, dt) * jnp.asarray(scale, dt)


def materialize_tree(specs, key: jax.Array, dtype: Optional[str] = None):
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    vals = [l.materialize(k, dtype) for l, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def tree_shardings(specs, ctx: ShardingCtx):
    """Map a ParamSpec tree to NamedShardings (or specs if mesh absent)."""
    return jax.tree_util.tree_map(
        lambda s: ctx.sharding(*s.axes) if ctx.mesh is not None
        else ctx.spec(*s.axes),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def tree_shapes(specs):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------------- #
# norms
# ---------------------------------------------------------------------- #
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * (1.0 + w.astype(jnp.float32))).astype(dt)


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    """LayerNorm with weight and bias; the weight is stored as an offset
    from 1, as the RMS norm's is."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    xc = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return (xc * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
            + b.astype(jnp.float32)).astype(dt)


def norm(x: jax.Array, p: Dict, cfg: ArchConfig,
         key: str = "norm") -> jax.Array:
    """The configuration's pre-norm, with parameters ``p[key]`` (and the
    LayerNorm's bias ``p[key + "_b"]``)."""
    if cfg.norm == "layernorm":
        return layernorm(x, p[key], p[key + "_b"], cfg.norm_eps)
    return rmsnorm(x, p[key], cfg.norm_eps)


def norm_specs(cfg: ArchConfig, key: str = "norm") -> Dict[str, ParamSpec]:
    specs = {key: ParamSpec((cfg.d_model,), (None,), init="zeros")}
    if cfg.norm == "layernorm":
        specs[key + "_b"] = ParamSpec((cfg.d_model,), (None,), init="zeros")
    return specs


# ---------------------------------------------------------------------- #
# rotary embeddings (RoPE and M-RoPE)
# ---------------------------------------------------------------------- #
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope_sections: Optional[Tuple[int, ...]] = None) -> jax.Array:
    """x: [b, s, h, d]; positions: [b, s] (RoPE) or [3, b, s] (M-RoPE).

    M-RoPE (Qwen2-VL): the head_dim/2 frequency slots are split into
    (temporal, height, width) sections, each rotated by its own position
    stream.  With text-only positions (all three equal) it reduces to
    standard RoPE.
    """
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # [d/2]
    if mrope_sections is not None:
        pos3 = positions.astype(jnp.float32)           # [3, b, s]
        secs = []
        off = 0
        for i, n in enumerate(mrope_sections):
            secs.append(pos3[i][..., None] * freqs[off:off + n])
            off += n
        angles = jnp.concatenate(secs, axis=-1)        # [b, s, d/2]
    else:
        angles = positions.astype(jnp.float32)[..., None] * freqs  # [b, s, d/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def mrope_sections_for(head_dim: int) -> Tuple[int, int, int]:
    """Qwen2-VL style (t, h, w) split of the d/2 frequency slots."""
    half = head_dim // 2
    t = half // 2
    h = (half - t) // 2
    w = half - t - h
    return (t, h, w)


# ---------------------------------------------------------------------- #
# attention (GQA, causal, optional sliding window)
# ---------------------------------------------------------------------- #
def attn_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    """QKV/O projection specs.  Attention projections are FSDP-2D sharded
    on the embed dim (head counts 24/40/48 do not divide the model axis)."""
    e, h, kvh, d = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    return {
        "wq": ParamSpec((e, h * d), ("fsdp2d", None)),
        "wk": ParamSpec((e, kvh * d), ("fsdp2d", None)),
        "wv": ParamSpec((e, kvh * d), ("fsdp2d", None)),
        "wo": ParamSpec((h * d, e), ("fsdp2d", None)),
        **norm_specs(cfg),
    }


def stack_specs(specs: Dict, n: int) -> Dict:
    """Prepend a stacked-layer axis to every ParamSpec in a tree."""
    return jax.tree_util.tree_map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init, s.dtype),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def _causal_mask(sq: int, skv: int, q_offset, window: int = 0) -> jax.Array:
    """[sq, skv] boolean mask.  q_offset = absolute position of q row 0."""
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(skv)[None, :]
    m = kpos <= qpos
    if window:
        m = jnp.logical_and(m, kpos > qpos - window)
    return m


def attention(x: jax.Array, p: Dict, cfg: ArchConfig, ctx: ShardingCtx,
              positions: jax.Array,
              cache: Optional[Dict] = None,
              window: int = 0,
              want_cache: bool = False) -> Tuple[jax.Array, Optional[Dict]]:
    """GQA attention.

    K/V caches are head-major, [b, kvh, S, d] per layer, so a layer's
    cache feeds the attention contractions as it lies in memory.

    Train/prefill: ``x`` is [b, s, e] (sequence-sharded over 'model'),
    cache is None (prefill returns the fresh cache).
    Decode: ``x`` is [b, 1, e]; ``cache`` holds this layer's k/v
    (sequence-sharded over 'model'), read only: the step attends over the
    cached positions before ``positions`` and over its own new key and
    value, and returns that row ({"k", "v"} [b, kvh, 1, d] in the cache's
    dtype) for the caller to write at ``positions``.
    """
    b, s, e = x.shape
    h, kvh, d = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    window = window or cfg.sliding_window
    xn = norm(x, p, cfg)
    cdt = xn.dtype

    q = (xn @ p["wq"].astype(cdt)).reshape(b, s, h, d)
    k = (xn @ p["wk"].astype(cdt)).reshape(b, s, kvh, d)
    v = (xn @ p["wv"].astype(cdt)).reshape(b, s, kvh, d)

    msecs = mrope_sections_for(d) if cfg.rope == "mrope" else None
    if cfg.rope in ("rope", "mrope"):    # abs_sin adds positions to x
        q = apply_rope(q, positions, cfg.rope_theta, msecs)
        k = apply_rope(k, positions, cfg.rope_theta, msecs)

    g = h // kvh
    if cache is not None:
        o, new_row = _decode_attention(q.reshape(b, kvh, g, d),
                                       k.reshape(b, kvh, 1, d),
                                       v.reshape(b, kvh, 1, d),
                                       cache, ctx, positions, window)
        return o.reshape(b, s, h * d) @ p["wo"].astype(cdt), new_row

    new_cache = None
    if want_cache:
        kc = constrain(k.transpose(0, 2, 1, 3), ctx,
                       "batch", "kv_heads", "kv_seq", "head_dim")
        vc = constrain(v.transpose(0, 2, 1, 3), ctx,
                       "batch", "kv_heads", "kv_seq", "head_dim")
        new_cache = {"k": kc, "v": vc}
    mask = _causal_mask(s, s, 0, window)[None, None, None, :, :]
    qg = q.reshape(b, s, kvh, g, d)
    # scores: [b, kvh, g, sq, skv]
    scores = jnp.einsum("bsknd,btkd->bknst", qg, k).astype(jnp.float32)
    scores = scores / np.sqrt(d)
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(cdt)
    o = jnp.einsum("bknst,btkd->bsknd", w, v).reshape(b, s, h * d)
    out = o @ p["wo"].astype(cdt)
    return out, new_cache


def _decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      cache: Dict, ctx: ShardingCtx, positions: jax.Array,
                      window: int) -> Tuple[jax.Array, Dict]:
    """One query position over the cache and its own key/value row.

    q [b, kvh, g, d]; k, v [b, kvh, 1, d]; cache k/v [b, kvh, S, d].
    The cached positions before the query's and the new row are the keys
    a cache with the row written in would give (positions <= the
    query's, within the window); their float32 scores share one softmax.
    Returns (o [b, kvh, g, d], {"k", "v"} rows in the cache's dtype)."""
    ck = constrain(cache["k"], ctx, "batch", "kv_heads", "kv_seq", "head_dim")
    cv = constrain(cache["v"], ctx, "batch", "kv_heads", "kv_seq", "head_dim")
    row = {"k": k.astype(ck.dtype), "v": v.astype(cv.dtype)}
    cdt = q.dtype
    k, v = row["k"].astype(cdt), row["v"].astype(cdt)
    scale = 1.0 / np.sqrt(q.shape[-1])
    skv = ck.shape[2]
    kpos = jnp.arange(skv)
    ppos = positions if positions.ndim == 2 else positions[0]  # mrope: t
    mask = kpos[None, :] < ppos[:, :1]                   # [b, skv]
    if window:
        mask = jnp.logical_and(mask, kpos[None, :] > ppos[:, :1] - window)
    # scores: [b, kvh, g, skv] over the cache, [b, kvh, g, 1] over the row
    sc = jnp.einsum("bknd,bktd->bknt", q, ck.astype(cdt)).astype(jnp.float32)
    sn = jnp.einsum("bknd,bktd->bknt", q, k).astype(jnp.float32)
    sc = jnp.where(mask[:, None, None, :], sc * scale, -1e30)
    w = jax.nn.softmax(jnp.concatenate([sc, sn * scale], axis=-1),
                       axis=-1).astype(cdt)
    o = jnp.einsum("bknt,bktd->bknd", w[..., :skv], cv.astype(cdt),
                   preferred_element_type=jnp.float32) + \
        jnp.einsum("bknt,bktd->bknd", w[..., skv:], v,
                   preferred_element_type=jnp.float32)
    return o.astype(cdt), row


# ---------------------------------------------------------------------- #
# cross-attention to conditioning states (MusicGen's text)
# ---------------------------------------------------------------------- #
def xattn_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    """Q from the stream, K/V from the projected conditioning states
    (width d_model), O back; every head attends (no grouping)."""
    e, h, d = cfg.d_model, cfg.n_heads, cfg.hd
    return {
        "wq": ParamSpec((e, h * d), ("fsdp2d", None)),
        "wk": ParamSpec((e, h * d), ("fsdp2d", None)),
        "wv": ParamSpec((e, h * d), ("fsdp2d", None)),
        "wo": ParamSpec((h * d, e), ("fsdp2d", None)),
        **norm_specs(cfg),
    }


def cond_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    """Projection of the conditioning states to the model width."""
    return {"w": ParamSpec((cfg.cond_dim, cfg.d_model), (None, "fsdp")),
            "b": ParamSpec((cfg.d_model,), (None,), init="zeros")}


def project_cond(cond: jax.Array, p: Dict, cfg: ArchConfig,
                 ctx: ShardingCtx) -> jax.Array:
    """cond [b, t, cond_dim] -> [b, t, d_model]: cond @ w + b."""
    cdt = jnp.dtype(cfg.dtype)
    out = cond.astype(cdt) @ p["w"].astype(cdt) + p["b"].astype(cdt)
    return constrain(out, ctx, "batch", None, "embed")


def cross_attention(x: jax.Array, p: Dict, cond: jax.Array,
                    cond_mask: jax.Array, cfg: ArchConfig,
                    ctx: ShardingCtx) -> jax.Array:
    """Pre-norm cross-attention of ``x`` [b, s, e] over the projected
    conditioning states ``cond`` [b, t, e]: no causal mask, and key
    positions where ``cond_mask`` [b, t] is False (padding) are left
    out of every softmax."""
    b, s, _ = x.shape
    t = cond.shape[1]
    h, d = cfg.n_heads, cfg.hd
    xn = norm(x, p, cfg)
    cdt = xn.dtype
    q = (xn @ p["wq"].astype(cdt)).reshape(b, s, h, d)
    k = (cond @ p["wk"].astype(cdt)).reshape(b, t, h, d)
    v = (cond @ p["wv"].astype(cdt)).reshape(b, t, h, d)
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32)
    scores = jnp.where(cond_mask[:, None, None, :], scores / np.sqrt(d),
                       -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(cdt)
    o = jnp.einsum("bhst,bthd->bshd", w, v).reshape(b, s, h * d)
    return o @ p["wo"].astype(cdt)


# ---------------------------------------------------------------------- #
# MLPs
# ---------------------------------------------------------------------- #
def mlp_specs(cfg: ArchConfig, d_ff: Optional[int] = None) -> Dict[str, ParamSpec]:
    e, f = cfg.d_model, (d_ff or cfg.d_ff)
    specs = {
        "w_up": ParamSpec((e, f), ("fsdp", "tp")),
        "w_down": ParamSpec((f, e), ("tp", "fsdp")),
        **norm_specs(cfg),
    }
    if cfg.mlp_act == "swiglu":
        specs["w_gate"] = ParamSpec((e, f), ("fsdp", "tp"))
    return specs


def mlp(x: jax.Array, p: Dict, cfg: ArchConfig, ctx: ShardingCtx,
        normed: bool = False) -> jax.Array:
    cdt = x.dtype
    xn = x if normed else norm(x, p, cfg)
    up = xn @ p["w_up"].astype(cdt)
    if cfg.mlp_seq_sharded:
        # §Perf: keep the [b, s, f] intermediate sequence-sharded so the
        # (small) weights gather instead of the (large) activations
        up = constrain(up, ctx, "batch", "seq", None)
    if cfg.mlp_act == "swiglu":
        gate = xn @ p["w_gate"].astype(cdt)
        if cfg.mlp_seq_sharded:
            gate = constrain(gate, ctx, "batch", "seq", None)
        hmid = jax.nn.silu(gate) * up
    elif cfg.mlp_act == "relu2":
        r = jax.nn.relu(up)
        hmid = r * r
    elif cfg.mlp_act == "gelu_exact":
        hmid = jax.nn.gelu(up, approximate=False)
    else:
        hmid = jax.nn.gelu(up)
    out = hmid @ p["w_down"].astype(cdt)
    if cfg.mlp_seq_sharded:
        out = constrain(out, ctx, "batch", "seq", "embed")
    return out


# ---------------------------------------------------------------------- #
# embeddings / head
# ---------------------------------------------------------------------- #
def embed_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    v, e = cfg.vocab, cfg.d_model
    vocab_ax = "vocab" if v % 256 == 0 else None   # mamba2's 50280 is odd
    emb_e_ax = "fsdp" if vocab_ax else "fsdp2d"
    specs = {
        "embedding": ParamSpec((v, e), (vocab_ax, emb_e_ax), init="small"),
        "final_norm": ParamSpec((e,), (None,), init="zeros"),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((e, v), (emb_e_ax, vocab_ax), init="small")
    return specs


def embed_tokens(tokens: jax.Array, p: Dict, cfg: ArchConfig,
                 ctx: ShardingCtx) -> jax.Array:
    x = jnp.take(p["embedding"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    return constrain(x, ctx, "batch", "seq", "embed")


def lm_logits(x: jax.Array, p: Dict, cfg: ArchConfig, ctx: ShardingCtx) -> jax.Array:
    xn = rmsnorm(x, p["final_norm"], cfg.norm_eps)
    head = p["embedding"].T if cfg.tie_embeddings else p["lm_head"]
    if cfg.seq_sharded_loss:
        # §Perf: keep the token dim sequence-sharded and gather the head
        # fully (one ~0.5-1GB bf16 all-gather per step) instead of the
        # per-step partial-sum all-reduce cascade over [b, s, v].
        cdt = jnp.dtype(cfg.dtype)
        logits = jax.lax.dot_general(
            xn.astype(cdt), head.astype(cdt),
            (((xn.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return constrain(logits, ctx, "batch", "seq", None)
    if cfg.cast_params_once:
        # §Perf: bf16 inputs with fp32 accumulation — halves the head
        # all-gather and the logits buffer without hurting the softmax
        # numerics (the reduction stays fp32).
        cdt = jnp.dtype(cfg.dtype)
        logits = jax.lax.dot_general(
            xn.astype(cdt), head.astype(cdt),
            (((xn.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        logits = xn.astype(jnp.float32) @ head.astype(jnp.float32)
    return constrain(logits, ctx, "batch", "seq", "vocab")


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  onehot: bool = False) -> jax.Array:
    """Mean token cross-entropy; logits [b, s, v] fp32, labels [b, s].

    ``onehot=True`` (§Perf): the gold logit is reduced through a fused
    iota==label select instead of take_along_axis — the gather lowers to
    s32 all-gathers + all-to-alls when vocab is sharded; the select
    partitions cleanly along the sharded vocab dim."""
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    if onehot:
        v = logits.shape[-1]
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                        logits.ndim - 1)
        hit = (iota == labels[..., None])
        gold = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    else:
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------- #
# codebooks (MusicGen): summed embeddings in, one head per codebook out
# ---------------------------------------------------------------------- #
def codebook_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    """Per codebook an embedding of ``vocab + 1`` rows (the codes and the
    special token ``vocab``) and a bias-free head to ``vocab`` logits;
    the final norm."""
    k, v, e = cfg.n_codebooks, cfg.vocab, cfg.d_model
    return {
        "codebooks": ParamSpec((k, v + 1, e), (None, None, "fsdp")),
        "heads": ParamSpec((k, e, v), (None, "fsdp", "vocab")),
        **norm_specs(cfg, "final_norm"),
    }


def embed_codes(codes: jax.Array, p: Dict, cfg: ArchConfig,
                ctx: ShardingCtx) -> jax.Array:
    """codes [b, K, s] -> the sum over codebooks of each one's embedding
    of its code, [b, s, e]."""
    x = sum(jnp.take(p["codebooks"][k], codes[:, k], axis=0)
            for k in range(cfg.n_codebooks))
    return constrain(x.astype(jnp.dtype(cfg.dtype)), ctx,
                     "batch", "seq", "embed")


def codebook_logits(x: jax.Array, p: Dict, cfg: ArchConfig,
                    ctx: ShardingCtx) -> jax.Array:
    """Final norm, then each codebook's head: logits [b, K, s, vocab]
    in float32."""
    xn = norm(x, p, cfg, "final_norm")
    logits = jnp.einsum("bse,kev->bksv", xn.astype(jnp.float32),
                        p["heads"].astype(jnp.float32))
    return constrain(logits, ctx, "batch", None, "seq", "vocab")


def codebook_cross_entropy(logits: jax.Array, labels: jax.Array,
                           special: int) -> jax.Array:
    """Mean over codebooks of each codebook's token-mean cross-entropy.

    logits [b, K, s, v] float32; labels [b, K, s], where a target equal
    to ``special`` (the delay pattern's filler) is left out of its
    codebook's mean."""
    valid = labels != special
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.where(valid, labels, 0)[..., None],
                               axis=-1)[..., 0]
    nll = jnp.where(valid, logz - gold, 0.0)
    count = jnp.maximum(jnp.sum(valid, axis=(0, 2)), 1)
    return jnp.mean(jnp.sum(nll, axis=(0, 2)) / count)
