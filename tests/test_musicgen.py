"""musicgen-medium: the program's decoder against the plain reference
(``musicgen_reference.py``) on seeded random weights, the delay pattern
and its loss mask, cross-attention's padding mask, phi4's graph left as
it was, and elastic training 2 -> 4 -> 2 devices (in a subprocess with
four host devices)."""
import dataclasses
import hashlib
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import musicgen_reference as ref
from repro.configs.registry import get_config
from repro.data.pipeline import DataConfig, SyntheticTokenPipeline, \
    delay_pattern
from repro.models.config import ShapeConfig
from repro.models.layers import codebook_cross_entropy
from repro.models.model import make_model
from repro.models.transformer import forward

ROOT = Path(__file__).resolve().parents[1]
SHAPE = ShapeConfig("t", 24, 3, "train", cond_len=12)


@pytest.fixture(scope="module")
def tiny():
    """Reduced musicgen (2 layers, width 64, 4 heads of 16, 4 codebooks of
    256 codes, text width 32) in float32, every parameter random: the
    norms' weights and biases too."""
    cfg = get_config("musicgen-medium").reduced()
    model = make_model(cfg)
    params = model.init_params(jax.random.key(1))
    leaves, tdef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(2), len(leaves))
    params = tdef.unflatten([p + 0.1 * jax.random.normal(k, p.shape)
                             for p, k in zip(leaves, keys)])
    batch = {k: jnp.asarray(v) for k, v in SyntheticTokenPipeline(
        cfg, SHAPE, DataConfig(seed=5)).batch_at(0).items()}
    return cfg, model, params, batch


def test_config_is_musicgen_medium():
    cfg = get_config("musicgen-medium")
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.hd, cfg.d_ff) == \
        (1536, 48, 24, 64, 6144)
    assert (cfg.n_codebooks, cfg.vocab, cfg.cond_dim) == (4, 2048, 768)
    assert (cfg.norm, cfg.mlp_act, cfg.rope, cfg.frontend) == \
        ("layernorm", "gelu_exact", "abs_sin", "codebooks")
    # 16 layers: 37.75 M a layer, 25.2 M in embeddings, heads, projection
    cut = dataclasses.replace(cfg, n_layers=16)
    shapes = make_model(cut).param_shapes()
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert n == 16 * (8 * 1536 * 1536 + 2 * 1536 * 6144 + 6 * 1536) \
        + 4 * 2049 * 1536 + 4 * 1536 * 2048 + 2 * 1536 + 768 * 1536 + 1536


def test_loss_and_grads_match_reference(tiny):
    """Tolerances: both sides are float32 on the CPU (the reference at
    HIGHEST, which there is plain float32 too); they differ in the order
    of reductions only, some 1e-7 relative per sum, grown through two
    layers and the backward pass.  1e-5 on the loss and 1e-4 by norm on
    each gradient leaf are well above that and far below what a wrong
    mask, norm, activation or position table gives (1e-2 and more)."""
    cfg, model, params, batch = tiny
    loss, grads = jax.value_and_grad(model.eval_step)(params, batch)
    rloss, rgrads = jax.value_and_grad(ref.loss)(params, batch, cfg.n_heads)
    assert abs(float(loss) - float(rloss)) <= 1e-5 * abs(float(rloss))
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        r = rgrads
        for k in path:
            r = r[k.key]
        gap = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert gap <= 1e-4, (jax.tree_util.keystr(path), gap)


def test_logits_match_reference(tiny):
    cfg, model, params, batch = tiny
    got, _ = forward(params, cfg, model.ctx, codes=batch["codes"],
                     cond=batch["cond"], cond_mask=batch["cond_mask"])
    want = ref.forward(params, batch["codes"], batch["cond"],
                       batch["cond_mask"], cfg.n_heads)
    assert got.shape == (3, 4, 24, 256)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_delay_pattern():
    raw = np.arange(4 * 6).reshape(1, 4, 6)
    d = delay_pattern(raw, -1)[0]
    for k in range(4):
        assert (d[k, :k] == -1).all()
        np.testing.assert_array_equal(d[k, k:], raw[0, k, :6 - k])
    cfg = get_config("musicgen-medium").reduced()
    b = SyntheticTokenPipeline(cfg, SHAPE).batch_at(3)
    codes, labels, special = b["codes"], b["labels"], cfg.vocab
    np.testing.assert_array_equal(labels[..., :-1], codes[..., 1:])
    for k in range(4):
        assert (codes[:, k, :k] == special).all()
        assert (codes[:, k, k:] != special).all()
        # targets on the filler: the first k - 1 of codebook k
        assert (labels[:, k, :max(k - 1, 0)] == special).all()
        assert (labels[:, k, max(k - 1, 0):] != special).all()


def test_loss_leaves_out_masked_targets():
    """Each codebook's mean runs over its own valid targets; the logits
    at masked targets change nothing."""
    rng = np.random.default_rng(0)
    v = 16
    logits = rng.standard_normal((2, 4, 5, v)).astype(np.float32)
    labels = rng.integers(0, v, (2, 4, 5))
    labels[:, 2, :1] = v
    labels[:, 3, :2] = v
    got = float(codebook_cross_entropy(jnp.asarray(logits),
                                       jnp.asarray(labels), v))
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    per_k = []
    for k in range(4):
        keep = labels[:, k] != v
        nll = -np.take_along_axis(logp[:, k], np.where(keep, labels[:, k],
                                                       0)[..., None], -1)[..., 0]
        per_k.append(nll[keep].mean())
    assert got == pytest.approx(np.mean(per_k), rel=1e-6)
    logits[:, 3, :2] += 50.0 * rng.standard_normal((2, 2, v))
    again = float(codebook_cross_entropy(jnp.asarray(logits),
                                         jnp.asarray(labels), v))
    assert again == pytest.approx(got, rel=1e-6)


def test_padded_text_keys_change_nothing(tiny):
    cfg, model, params, batch = tiny
    mask = batch["cond_mask"]
    assert not bool(jnp.all(mask))          # some rows are padded
    garbage = 7.0 * jax.random.normal(jax.random.key(3), batch["cond"].shape)
    noisy = jnp.where(mask[..., None], batch["cond"], garbage)

    def logits(cond, m):
        return forward(params, cfg, model.ctx, codes=batch["codes"],
                       cond=cond, cond_mask=m)[0]
    base = logits(batch["cond"], mask)
    np.testing.assert_array_equal(logits(noisy, mask), base)
    # the mask is what keeps them out: without it the garbage shows
    assert float(jnp.max(jnp.abs(logits(noisy, jnp.ones_like(mask))
                                 - base))) > 1e-2


# the jaxprs of phi4-mini's reduced train, prefill and serve steps, as
# traced before the codebook and cross-attention options were added
PHI4_JAXPRS = {"train": "d649132cf62cb6aa", "prefill": "7e29d48274823453",
               "serve": "80d9f51069c38e42"}


def test_phi4_traces_the_same_ops():
    m = make_model(get_config("phi4-mini-3.8b").reduced())
    sd = jax.ShapeDtypeStruct
    b, s = 2, 16
    tok = {"tokens": sd((b, s), jnp.int32)}
    cache = m.cache_specs(ShapeConfig("d", s, b, "decode"))
    calls = {
        "train": (m.train_step, (m.param_shapes(), m.opt_shapes(),
                                 dict(tok, labels=sd((b, s), jnp.int32)))),
        "prefill": (m.prefill_step, (m.param_shapes(), tok)),
        "serve": (m.serve_step, (m.param_shapes(), cache,
                                 {"tokens": sd((b, 1), jnp.int32)},
                                 sd((), jnp.int32)))}
    got = {name: hashlib.sha256(str(jax.make_jaxpr(f)(*args)).encode())
           .hexdigest()[:16] for name, (f, args) in calls.items()}
    assert got == PHI4_JAXPRS


# Elastic 2 -> 4 -> 2 against a fixed two-device run, in float32 on the
# CPU: the meshes differ only in the order of the gradient reductions,
# about 1e-7 relative a sum, grown through a few AdamW steps; rtol 1e-4
# is far above that and far below a lost or reset state (1e-2 and more).
ELASTIC_LOSS_RTOL = 1e-4

ELASTIC = """
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs.registry import get_config
from repro.core import Instance, SchedulerInstance, build_tpu_fleet
from repro.data.pipeline import DataConfig, SyntheticTokenPipeline
from repro.models.config import ShapeConfig
from repro.optim.adamw import OptConfig
from repro.runtime.elastic import ElasticRuntime

cfg = get_config("musicgen-medium").reduced()
shape = ShapeConfig("t", 16, 8, "train", cond_len=8)
opt = OptConfig(warmup=2, total_steps=20)
pipe = SyntheticTokenPipeline(cfg, shape, DataConfig(seed=3))
compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda ev, d, **_: compiles.append(ev)
    if ev == "/jax/core/compile/backend_compile_duration" else None)

@jax.jit
def checksums(tree):
    def one(x):
        u = jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
        return jnp.sum(u * (jnp.arange(u.size, dtype=jnp.uint32) + 1),
                       dtype=jnp.uint32)
    return jnp.stack([one(x) for x in jax.tree_util.tree_leaves(tree)])

class Runtime(ElasticRuntime):
    reset_moments = False
    def bind(self, key=None):
        resharding = self.params is not None
        super().bind(key)
        if self.reset_moments and resharding:
            self.opt_state = self.opt_state._replace(
                mu=jax.tree_util.tree_map(jnp.zeros_like, self.opt_state.mu))

def runtime(chips):
    fleet = build_tpu_fleet(pods=1, racks_per_pod=1, nodes_per_rack=1,
                            chips_per_node=4)
    rt = Runtime(Instance(SchedulerInstance("host", fleet)), cfg, shape,
                 chip_type="chip", opt=opt)
    assert rt.allocate(chips)
    rt.bind(jax.random.key(0))
    return rt

def resize(rt, how):
    before = np.asarray(checksums((rt.params, rt.opt_state)))
    assert getattr(rt, how)(2)
    after = np.asarray(checksums((rt.params, rt.opt_state)))
    return int(np.sum(before != after))

fixed = runtime(2)
want = [float(fixed.step(pipe.batch_at(i))["loss"]) for i in range(8)]

rt = runtime(2)
rt.prepare([2, 4])
assert rt.n_step_builds == 2
got, mismatch = [], []
for i in range(8):
    if i == 5:          # both sizes have stepped: the rest is warm
        n0 = len(compiles)
    if i in (2, 5):
        mismatch.append(resize(rt, "grow"))
        assert rt.mesh.size == 4
    if i in (3, 6):
        mismatch.append(resize(rt, "shrink"))
        assert rt.mesh.size == 2
    got.append(float(rt.step(pipe.batch_at(i))["loss"]))
warm = len(compiles) - n0
np.testing.assert_allclose(got, want, rtol={rtol})
assert mismatch == [0, 0, 0, 0], mismatch
assert rt.n_step_builds == 2 and rt.n_resizes == 4
assert warm == 0, warm
nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(
    (rt.params, rt.opt_state)))
assert rt.reshard_bytes == 4 * nbytes
assert rt.reshard_fallback_bytes == 0

bad = runtime(2)
bad.reset_moments = True
bad.step(pipe.batch_at(0))
assert resize(bad, "grow") > 0
print("ELASTIC_OK", got, want)
"""


def test_elastic_2_4_2_matches_fixed_mesh():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", ELASTIC.replace("{rtol}",
                                                repr(ELASTIC_LOSS_RTOL))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "ELASTIC_OK" in out.stdout
