"""Plain float32 reference of MusicGen's decoder and its training loss
(arXiv:2306.05284; the Hugging Face config of facebook/musicgen-medium),
for the CPU tests.  It imports nothing of the program.

It reads the program's parameter layout:

    embed:  codebooks [K, V+1, E], heads [K, E, V], final_norm[_b] [E]
    cond:   w [C, E], b [E]
    blocks: attn / xattn {wq, wk, wv [L, E, E], wo [L, E, E], norm[_b] [L, E]}
            mlp {w_up [L, E, F], w_down [L, F, E], norm[_b] [L, E]}

Norm weights are offsets from 1.  The pass: x_t = sum_k E_k[c_k,t] +
PE(t) (PE = [cos(t w_j), sin(t w_j)], w_j = 10000^(-j/(h-1)), h = E/2);
per layer, pre-LN causal self-attention, cross-attention to the
projected text states T W_p + b_p with a key padding mask, and an
exact-GELU FFN; a final LayerNorm and K bias-free heads.  The loss is
the mean over codebooks of each one's token-mean cross-entropy, targets
equal to the special token ``V`` left out.  Every product is float32 at
``Precision.HIGHEST``.
"""
import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _ln(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * (1.0 + w) + b


def _mm(x, w):
    return jnp.matmul(x, w, precision=HI)


def _gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / np.sqrt(2.0)))


def positions(s, e):
    h = e // 2
    w = 10000.0 ** (-np.arange(h) / (h - 1))
    ang = np.arange(s)[:, None] * w[None, :]
    return jnp.asarray(np.concatenate([np.cos(ang), np.sin(ang)], -1),
                       jnp.float32)


def _attend(q, k, v, mask):
    """q [b, s, H, D], k/v [b, t, H, D], mask broadcast to [b, H, s, t]."""
    sc = jnp.einsum("bshd,bthd->bhst", q, k, precision=HI) \
        / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p, v, precision=HI)


def forward(params, codes, cond, cond_mask, n_heads, eps=1e-5):
    """Logits [b, K, s, V] of codes [b, K, s] given text states cond
    [b, t, C] with mask [b, t]."""
    emb, blocks = params["embed"], params["blocks"]
    b, n_codebooks, s = codes.shape
    x = sum(emb["codebooks"][k][codes[:, k]] for k in range(n_codebooks))
    e = x.shape[-1]
    x = x + positions(s, e)[None]
    c = _mm(cond, params["cond"]["w"]) + params["cond"]["b"]
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None]
    keys = cond_mask[:, None, None, :]

    def heads(y):
        return y.reshape(y.shape[0], y.shape[1], n_heads, -1)

    for i in range(blocks["attn"]["wq"].shape[0]):
        a = {k: v[i] for k, v in blocks["attn"].items()}
        xa = {k: v[i] for k, v in blocks["xattn"].items()}
        m = {k: v[i] for k, v in blocks["mlp"].items()}
        h = _ln(x, a["norm"], a["norm_b"], eps)
        o = _attend(heads(_mm(h, a["wq"])), heads(_mm(h, a["wk"])),
                    heads(_mm(h, a["wv"])), causal)
        x = x + _mm(o.reshape(b, s, e), a["wo"])
        h = _ln(x, xa["norm"], xa["norm_b"], eps)
        o = _attend(heads(_mm(h, xa["wq"])), heads(_mm(c, xa["wk"])),
                    heads(_mm(c, xa["wv"])), keys)
        x = x + _mm(o.reshape(b, s, e), xa["wo"])
        h = _ln(x, m["norm"], m["norm_b"], eps)
        x = x + _mm(_gelu(_mm(h, m["w_up"])), m["w_down"])
    xn = _ln(x, emb["final_norm"], emb["final_norm_b"], eps)
    return jnp.einsum("bse,kev->bksv", xn, emb["heads"], precision=HI)


def loss(params, batch, n_heads, eps=1e-5):
    logits = forward(params, batch["codes"], batch["cond"],
                     batch["cond_mask"], n_heads, eps)
    special = logits.shape[-1]
    labels = batch["labels"]
    valid = labels != special
    logp = jax.nn.log_softmax(logits, axis=-1)
    gold = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None],
                               axis=-1)[..., 0]
    per_k = [-jnp.sum(jnp.where(valid[:, k], gold[:, k], 0.0))
             / jnp.sum(valid[:, k]) for k in range(labels.shape[1])]
    return sum(per_k) / len(per_k)
