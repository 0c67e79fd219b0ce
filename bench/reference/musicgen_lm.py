"""Plain reference for MusicGen's decoder (the musicgen-medium cell): a
float32 forward pass, its training loss, the loss's gradient, and one
AdamW step.

It imports nothing of the program.  It reads the training state in the
layout the system under test keeps it (norm weights stored as offsets
from 1, as the program's parametrisation of the same LayerNorm):

    embed:  codebooks [K, V+1, E], heads [K, E, V], final_norm[_b] [E]
    cond:   w [C, E], b [E]          (the text states' projection)
    blocks: attn, xattn {wq, wk, wv, wo [L, E, E], norm[_b] [L, E]}
            mlp {w_up [L, E, F], w_down [L, F, E], norm[_b] [L, E]}

The pass follows the configuration file and arXiv:2306.05284: x_t =
sum_k E_k[c_k,t] + PE(t), PE = [cos(t w_j), sin(t w_j)] with w_j =
10000^(-j/(h-1)), h = E/2, unscaled; per layer pre-LN causal
self-attention, cross-attention (no causal mask, padded text keys left
out) to the text states projected as T W_p + b_p, and W2 GELU_erf(W1 x);
a final LayerNorm and K bias-free heads.  The loss is the mean over
codebooks of each codebook's token-mean cross-entropy; targets equal to
the special token V are left out.  Products run in float32 at
``Precision.HIGHEST``, on blocks of rows (the loss splits over rows with
each codebook's whole-batch count of targets), each layer recomputed in
the backward pass so that a block's activations stay small.

AdamW (``adamw_step``) runs on the host in float32 numpy, in blocks of
rows on a pool of threads (``map_blocks``): global-norm clipping, then
m, v with bias correction, the update m^/(sqrt(v^) + eps), decoupled
weight decay on every leaf of two or more axes (the matrices, the
embeddings and the stacked layers' norms; not the final norm and the
biases), at the warmup-cosine learning rate of the step.

``fp8=True`` is the control: the same pass with every product through
a weight in float8 e4m3, the step below the bfloat16 the configuration
computes in.  Forward, the weight and the activation entering it are
rounded as in ``dense_lm`` (a scale per weight, one per row of the
activation); backward, the cotangent arriving at the product is rounded
the same way, with a scale of its own per row, and the two backward
products take the rounded operands.  The cotangent is scaled before it
is rounded: unscaled, the loss's cotangents (about 1e-9 at a cell's
size) lie below e4m3's smallest number and the gradient would vanish.
"""
from __future__ import annotations

import functools
import math
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .dense_lm import HI, _q8

BLOCK = 1 << 22         # elements in one block of rows, for the host's work


@jax.custom_vjp
def _mm8(x, w):
    return jnp.matmul(_q8(x, axis=-1), _q8(w, axis=None), precision=HI)


def _mm8_fwd(x, w):
    xq, wq = _q8(x, axis=-1), _q8(w, axis=None)
    return jnp.matmul(xq, wq, precision=HI), (xq, wq)


def _mm8_bwd(res, g):
    xq, wq = res
    gq = _q8(g, axis=-1)
    dx = jnp.matmul(gq, wq.T, precision=HI)
    dw = jnp.matmul(xq.reshape(-1, xq.shape[-1]).T,
                    gq.reshape(-1, gq.shape[-1]), precision=HI)
    return dx, dw


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def _mm(x, w, fp8: bool):
    w = w.astype(jnp.float32)
    if fp8:
        return _mm8(x, w)
    return jnp.matmul(x, w, precision=HI)


def _ln(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * (1.0 + w) + b


def _gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / np.sqrt(2.0)))


def positions(s: int, e: int) -> np.ndarray:
    h = e // 2
    w = 10000.0 ** (-np.arange(h) / (h - 1))
    ang = np.arange(s)[:, None] * w[None, :]
    return np.concatenate([np.cos(ang), np.sin(ang)], -1).astype(np.float32)


def _attend(q, k, v, mask):
    """q [b, s, H, D], k/v [b, t, H, D]; mask broadcasts to [b, H, s, t]."""
    sc = jnp.einsum("bshd,bthd->bhst", q, k, precision=HI) \
        / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p, v, precision=HI)


def _layer(x, lp, c, keys, n_heads, eps, fp8):
    b, s, e = x.shape

    def heads(y):
        return y.reshape(y.shape[0], y.shape[1], n_heads, -1)
    a, xa, m = lp["attn"], lp["xattn"], lp["mlp"]
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None]
    h = _ln(x, a["norm"], a["norm_b"], eps)
    o = _attend(heads(_mm(h, a["wq"], fp8)), heads(_mm(h, a["wk"], fp8)),
                heads(_mm(h, a["wv"], fp8)), causal)
    x = x + _mm(o.reshape(b, s, e), a["wo"], fp8)
    h = _ln(x, xa["norm"], xa["norm_b"], eps)
    o = _attend(heads(_mm(h, xa["wq"], fp8)), heads(_mm(c, xa["wk"], fp8)),
                heads(_mm(c, xa["wv"], fp8)), keys)
    x = x + _mm(o.reshape(b, s, e), xa["wo"], fp8)
    h = _ln(x, m["norm"], m["norm_b"], eps)
    return x + _mm(_gelu(_mm(h, m["w_up"], fp8)), m["w_down"], fp8)


def block_loss(params, codes, labels, cond, cond_mask, counts, *,
               n_heads: int, eps: float, fp8: bool):
    """This block of rows' share of the loss: per codebook, the summed
    cross-entropy of its valid targets over ``counts[k]``, the
    codebook's count in the whole batch; averaged over codebooks."""
    emb = params["embed"]
    n_cb, s = codes.shape[1], codes.shape[2]
    x = sum(jnp.take(emb["codebooks"][k], codes[:, k], axis=0)
            for k in range(n_cb))
    x = x + jnp.asarray(positions(s, x.shape[-1]))[None]
    c = _mm(cond, params["cond"]["w"], fp8) + params["cond"]["b"]
    keys = cond_mask[:, None, None, :]

    def body(x, lp):
        return _layer(x, lp, c, keys, n_heads, eps, fp8), None
    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["blocks"])
    xn = _ln(x, emb["final_norm"], emb["final_norm_b"], eps)
    special = emb["heads"].shape[-1]
    total = 0.0
    for k in range(n_cb):
        logp = jax.nn.log_softmax(_mm(xn, emb["heads"][k], fp8), axis=-1)
        valid = labels[:, k] != special
        gold = jnp.take_along_axis(
            logp, jnp.where(valid, labels[:, k], 0)[..., None], axis=-1)[..., 0]
        total = total - jnp.sum(jnp.where(valid, gold, 0.0)) / counts[k]
    return total / n_cb


@functools.partial(jax.jit, static_argnames=("n_heads", "eps", "fp8"),
                   donate_argnums=(1,))
def _accumulate(params, acc, codes, labels, cond, cond_mask, counts, *,
                n_heads, eps, fp8):
    loss, grads = jax.value_and_grad(block_loss)(
        params, codes, labels, cond, cond_mask, counts, n_heads=n_heads,
        eps=eps, fp8=fp8)
    return acc[0] + loss, jax.tree_util.tree_map(jnp.add, acc[1], grads)


def loss_and_grads(c: dict, params, batch: Dict[str, np.ndarray], device,
                   rows: int, fp8: bool = False) -> Tuple[float, Dict]:
    """Loss and gradient of the whole batch on ``device``, ``rows`` rows
    at a time.  ``params`` is a tree of arrays on any device; the gradient
    comes back on ``device``, in float32, in the same tree."""
    n_heads, eps = c["num_attention_heads"], c["layer_norm_eps"]
    p = jax.device_put(jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float32), params), device)
    special = c["vocab_size"]
    counts = np.sum(batch["labels"] != special, axis=(0, 2)).astype(np.float32)
    acc = (jnp.zeros((), jnp.float32, device=device),
           jax.tree_util.tree_map(
               lambda a: jnp.zeros(a.shape, jnp.float32, device=device), p))
    n = batch["codes"].shape[0]
    for lo in range(0, n, rows):
        part = [batch[k][lo:lo + rows] for k in
                ("codes", "labels", "cond", "cond_mask")]
        acc = _accumulate(p, acc, *jax.device_put(part + [counts], device),
                          n_heads=n_heads, eps=eps, fp8=fp8)
    return float(acc[0]), acc[1]


# ---------------------------------------------------------------------- #
# one AdamW step
# ---------------------------------------------------------------------- #
def learning_rate(opt: dict, step: int) -> float:
    """Linear warmup to ``lr`` over ``warmup`` steps, then cosine down to
    ``min_lr_frac`` of it at ``total_steps``."""
    lr, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return lr * step / max(warm, 1)
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    f = opt["min_lr_frac"]
    return lr * (f + (1 - f) * 0.5 * (1 + math.cos(math.pi * t)))


def _blocks(x: np.ndarray) -> List[slice]:
    """A leaf's first axis cut into blocks of about ``BLOCK`` elements."""
    n = x.shape[0] if x.ndim else 1
    k = max(1, min(n, x.size // BLOCK))
    cut = np.linspace(0, n, k + 1).astype(int)
    return [slice(a, b) for a, b in zip(cut[:-1], cut[1:])]


def _over_blocks(work, leaves) -> List:
    """``work(i, block)`` for every block of rows of every leaf ``i``, on a
    pool of threads (numpy's loops release the interpreter's lock)."""
    with ThreadPoolExecutor(os.cpu_count()) as pool:
        futs = [pool.submit(work, i, sl) for i, x in enumerate(leaves)
                for sl in _blocks(x)]
        return [f.result() for f in futs]


def map_blocks(fn, *trees):
    """The float32 tree of ``fn`` over aligned blocks of rows of the
    leaves of ``trees`` (host arrays of one structure)."""
    leaves, tdef = jax.tree_util.tree_flatten(trees[0])
    cols = [leaves] + [tdef.flatten_up_to(t) for t in trees[1:]]
    outs = [np.empty(x.shape, np.float32) for x in leaves]

    def work(i, sl):
        outs[i][sl] = fn(*(c[i][sl] for c in cols))
    _over_blocks(work, leaves)
    return tdef.unflatten(outs)


def sum_blocks(fn, *trees) -> float:
    """The sum of ``fn`` (a float) over aligned blocks of rows of the
    leaves of ``trees``."""
    leaves, tdef = jax.tree_util.tree_flatten(trees[0])
    cols = [leaves] + [tdef.flatten_up_to(t) for t in trees[1:]]
    return math.fsum(_over_blocks(
        lambda i, sl: float(fn(*(c[i][sl] for c in cols))), leaves))


def clipped(opt: dict, grads):
    """The gradient scaled to global norm at most ``grad_clip``."""
    norm = math.sqrt(sum_blocks(lambda g: np.sum(np.square(g)), grads))
    scale = np.float32(min(1.0, opt["grad_clip"] / max(norm, 1e-9)))
    return map_blocks(lambda g: g * scale, grads)


def adamw_step(opt: dict, params, grads, mu, nu, step: int):
    """The parameters after optimizer step ``step`` (counted from 1) with
    the raw gradient ``grads``; ``mu``, ``nu`` are the moments before the
    step.  Host arrays, float32."""
    f32 = np.float32
    b1, b2 = opt["betas"]
    lr = f32(learning_rate(opt, step))
    bc1, bc2 = f32(1 - b1 ** step), f32(1 - b2 ** step)
    eps, wd = f32(opt["eps"]), f32(opt["weight_decay"])
    b1, b2 = f32(b1), f32(b2)

    def one(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        u = (m / bc1) / (np.sqrt(v / bc2) + eps)
        decay = wd if p.ndim >= 2 else f32(0.0)
        return p - lr * decay * p - lr * u
    return map_blocks(one, params, clipped(opt, grads), mu, nu)
