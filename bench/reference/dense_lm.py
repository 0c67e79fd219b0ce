"""Plain reference for a dense decoder LM (the phi4-mini cell): seeded
weights, and a float32 forward pass over whole sequences.

It imports nothing of the program.  The weights are data the benchmark
makes from the seed, in one jitted call, straight into the serving
dtype (bfloat16), laid out as the system under test takes them:

    embed:  embedding [V, E], final_norm [E] (lm_head [E, V] if untied)
    blocks: attn {wq [L,E,H*D], wk/wv [L,E,K*D], wo [L,H*D,E], norm [L,E]}
            mlp  {w_gate/w_up [L,E,F], w_down [L,F,E], norm [L,E]}

The forward pass follows the configuration file: pre-norm blocks, RMS
norm scaled by ``1 + w`` (norm weights are stored as offsets from 1),
grouped-query causal attention with rotary position embedding over the
whole head (NeoX halves, ``rope_theta``), a SwiGLU MLP, a final norm and
the LM head (the embedding's transpose, where embeddings are tied).  Every product runs in float32 at
``Precision.HIGHEST`` on float32 copies of the bf16 weights, one layer
at a time so the float32 copies never exceed one layer.

``fp8=True`` is the control: the same pass with every weight and every
activation that enters a projection rounded to float8 e4m3 (one scale
per weight tensor, one per activation row), the step below the bf16
that the configuration states.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


# ---------------------------------------------------------------------- #
# weights from the seed
# ---------------------------------------------------------------------- #
def weight_shapes(c: dict) -> Dict:
    E, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    H, K, D = (c["num_attention_heads"], c["num_key_value_heads"],
               c["head_dim"])
    F = c["intermediate_size"]
    embed = {"embedding": (V, E), "final_norm": (E,)}
    if not c["tie_word_embeddings"]:
        embed["lm_head"] = (E, V)
    return {
        "embed": embed,
        "blocks": {
            "attn": {"wq": (L, E, H * D), "wk": (L, E, K * D),
                     "wv": (L, E, K * D), "wo": (L, H * D, E),
                     "norm": (L, E)},
            "mlp": {"w_gate": (L, E, F), "w_up": (L, E, F),
                    "w_down": (L, F, E), "norm": (L, E)},
        },
    }


def _scale(path: Tuple[str, ...], shape: Tuple[int, ...]) -> float:
    """Standard deviation of each weight: 1/sqrt(fan-in) for products,
    1/sqrt(width) for the embedding (with tied embeddings it is also
    the LM head, whose fan-in is the width), 0.1 for the norm offsets
    (scales of about 1 +- 0.1)."""
    name = path[-1]
    if name.endswith("norm"):
        return 0.1
    if name == "embedding":
        return 1.0 / np.sqrt(shape[-1])
    return 1.0 / np.sqrt(shape[-2])


def init_weights(c: dict, key_words: Tuple[int, int], dtype=jnp.bfloat16):
    """All weights in one jitted call on the default device."""
    shapes = weight_shapes(c)
    flat = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))[0]
    paths = [tuple(k.key for k in p) for p, _ in flat]
    treedef = jax.tree_util.tree_structure(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def make(kw):
        key = jax.random.wrap_key_data(kw)
        keys = jax.random.split(key, len(flat))
        leaves = [
            jax.random.normal(k, shape, dtype)
            * jnp.asarray(_scale(path, shape), dtype)
            for k, path, (_, shape) in zip(keys, paths, flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    kw = jnp.asarray(np.asarray(key_words, np.uint32))
    return make(kw)


# ---------------------------------------------------------------------- #
# forward
# ---------------------------------------------------------------------- #
def _q8(x: jax.Array, axis) -> jax.Array:
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    w = w.astype(jnp.float32)
    if fp8:
        x = _q8(x, axis=-1)
        w = _q8(w, axis=None)
    return jnp.matmul(x, w, precision=HI)


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x: jax.Array, theta: float, rot: int) -> jax.Array:
    """x [b, s, h, d]; rotate the first ``rot`` dims (NeoX halves)."""
    s = x.shape[1]
    freqs = 1.0 / (theta ** (np.arange(0, rot, 2, dtype=np.float32) / rot))
    ang = np.arange(s, dtype=np.float32)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(xr, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out, xp], axis=-1)


@functools.partial(jax.jit, static_argnames=("c", "fp8"))
def _layer(x, attn, mlp, c, fp8: bool):
    c = dict(c)
    b, s, _ = x.shape
    H, K, D = (c["num_attention_heads"], c["num_key_value_heads"],
               c["head_dim"])
    eps = c["rms_norm_eps"]
    rot = int(D * c["partial_rotary_factor"])
    h = _rms(x, attn["norm"], eps)
    q = _mm(h, attn["wq"], fp8).reshape(b, s, H, D)
    k = _mm(h, attn["wk"], fp8).reshape(b, s, K, D)
    v = _mm(h, attn["wv"], fp8).reshape(b, s, K, D)
    q = _rope(q, c["rope_theta"], rot)
    k = _rope(k, c["rope_theta"], rot)
    g = H // K
    q = q.reshape(b, s, K, g, D)
    sc = jnp.einsum("bskgd,btkd->bkgst", q, k, precision=HI) / np.sqrt(D)
    causal = np.tril(np.ones((s, s), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v, precision=HI)
    x = x + _mm(o.reshape(b, s, H * D), attn["wo"], fp8)
    h = _rms(x, mlp["norm"], eps)
    up = _mm(h, mlp["w_up"], fp8)
    gate = _mm(h, mlp["w_gate"], fp8)
    return x + _mm(jax.nn.silu(gate) * up, mlp["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("c", "fp8"))
def _head(x, embed, targets, c, fp8: bool):
    """Per position: the best logit, the target's logit, and the
    argmax; ``x`` [n, E] final hidden states, ``targets`` [n]."""
    c = dict(c)
    xn = _rms(x, embed["final_norm"], c["rms_norm_eps"])
    w = embed["embedding"].T if c["tie_word_embeddings"] \
        else embed["lm_head"]
    logits = _mm(xn, w, fp8)
    best = jnp.max(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return best, tgt, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _frozen(c: dict) -> Tuple:
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "rms_norm_eps", "rope_theta",
            "partial_rotary_factor", "tie_word_embeddings")
    return tuple((k, c[k]) for k in keys)


def final_hidden(c: dict, params, tokens: np.ndarray,
                 fp8: bool = False) -> jax.Array:
    """Hidden states after the last layer, [b, s, E] float32."""
    fc = _frozen(c)
    x = jnp.take(params["embed"]["embedding"], jnp.asarray(tokens),
                 axis=0).astype(jnp.float32)
    blocks = params["blocks"]
    for layer in range(c["num_hidden_layers"]):
        attn = {k: v[layer] for k, v in blocks["attn"].items()}
        mlp = {k: v[layer] for k, v in blocks["mlp"].items()}
        x = _layer(x, attn, mlp, fc, fp8)
    return x


def served_gaps(c: dict, params, seqs: np.ndarray, served: np.ndarray,
                prompt_len: int, control: bool = False) -> np.ndarray:
    """Gaps of a greedy continuation against the float32 reference.

    ``seqs`` [b, prompt_len + n - 1]: each prompt and its ``n`` served
    tokens but the last; ``served`` [b, n].  Position ``prompt_len-1+j``
    predicted ``served[:, j]``.  Returns [b, n]: how far the reference's
    logit of that token lies below its best (0 where it is the
    reference's argmax).

    ``control=True`` reads the control instead: at each position the
    token the fp8 pass puts first takes the served token's place."""
    fc = _frozen(c)
    b, n = served.shape
    sel = slice(prompt_len - 1, prompt_len - 1 + n)
    targets = served
    if control:
        x8 = final_hidden(c, params, seqs, fp8=True)[:, sel]
        _, _, first = _head(x8.reshape(b * n, -1), params["embed"],
                            jnp.zeros((b * n,), jnp.int32), fc, True)
        targets = np.asarray(first).reshape(b, n)
        del x8
    x = final_hidden(c, params, seqs)[:, sel]
    best, tgt, _ = _head(x.reshape(b * n, -1), params["embed"],
                         jnp.asarray(targets.reshape(-1), jnp.int32), fc,
                         False)
    return np.asarray(best - tgt).reshape(b, n)
