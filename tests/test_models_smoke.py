"""Per-architecture smoke tests: reduced config, one train/serve step on
CPU, asserting shapes and no NaNs (the FULL configs are exercised only
via the dry-run)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, get_config
from repro.models.config import ShapeConfig
from repro.models.model import make_model


def _batches(cfg, b=2, s=32):
    if cfg.is_codebook:
        from repro.data.pipeline import SyntheticTokenPipeline
        train = SyntheticTokenPipeline(
            cfg, ShapeConfig("t", s, b, "train", cond_len=8)).batch_at(0)
        return {k: jnp.asarray(v) for k, v in train.items()}, None
    stub = cfg.frontend != "token"
    if stub:
        train = {"embeds": jnp.ones((b, s, cfg.d_model), jnp.float32),
                 "labels": jnp.zeros((b, s), jnp.int32)}
        dec = {"embeds": jnp.ones((b, 1, cfg.d_model), jnp.float32)}
    else:
        train = {"tokens": jnp.ones((b, s), jnp.int32),
                 "labels": jnp.zeros((b, s), jnp.int32)}
        dec = {"tokens": jnp.ones((b, 1), jnp.int32)}
    return train, dec


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_arch_smoke(arch_id, rng_key):
    cfg = get_config(arch_id).reduced()
    model = make_model(cfg)
    params = model.init_params(rng_key)
    opt = model.init_opt(params)
    train, dec = _batches(cfg)
    b, s = train["labels"].shape[0], train["labels"].shape[-1]

    p2, o2, metrics = jax.jit(model.train_step)(params, opt, train)
    assert np.isfinite(float(metrics["loss"]))
    # params actually changed
    l0 = jax.tree_util.tree_leaves(params)[0]
    l1 = jax.tree_util.tree_leaves(p2)[0]
    assert not np.allclose(np.asarray(l0), np.asarray(l1))

    prompt = {k: v for k, v in train.items() if k != "labels"}
    if cfg.is_codebook:     # trains only: serving needs a cross-attn cache
        with pytest.raises(NotImplementedError, match="codebook"):
            model.prefill_step(params, prompt)
        with pytest.raises(NotImplementedError, match="codebook"):
            model.serve_step(params, {}, prompt, jnp.int32(0))
        return
    logits, cache = jax.jit(model.prefill_step)(params, prompt)
    assert logits.shape == (b, 1, cfg.vocab)
    lg, cache2 = jax.jit(model.serve_step)(params, cache, dec,
                                           jnp.int32(s - 1))
    assert lg.shape == (b, 1, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(lg)))
    assert jax.tree_util.tree_structure(cache2) == \
        jax.tree_util.tree_structure(cache)


KV_LEAVES = ("k", "v", "shared_k", "shared_v")
# the four decode_step branches: dense, SSM, hybrid, MoE every layer
DECODE_ARCHS = ["llama3.2-3b", "mamba2-2.7b", "zamba2-2.7b",
                "qwen3-moe-30b-a3b"]


def _decode_model(arch_id, rng_key):
    import dataclasses
    cfg = get_config(arch_id).reduced()
    # dispatch MoE drops tokens at tiny capacity; use the dense oracle
    if cfg.is_moe:
        cfg = dataclasses.replace(cfg, moe_impl="dense")
    model = make_model(cfg)
    return cfg, model, model.init_params(rng_key)


def _grow(cache, extra):
    """Give the KV caches ``extra`` zero slots on the sequence axis
    ([..., kv_heads, seq, head_dim]); SSM conv/ssm states keep their
    exact shapes."""
    def grow(name, a):
        if name not in KV_LEAVES:
            return a
        pad_width = [(0, 0)] * a.ndim
        pad_width[a.ndim - 2] = (0, extra)
        return jnp.pad(a, pad_width)
    return {k: grow(k, v) for k, v in cache.items()}


@pytest.mark.parametrize("arch_id", DECODE_ARCHS)
def test_decode_consistent_with_forward(arch_id, rng_key):
    """prefill(s tokens) + decode(token s) must equal a full forward over
    s+1 tokens at the last position — validates the cache path."""
    cfg, model, params = _decode_model(arch_id, rng_key)
    s = 16
    toks = jax.random.randint(jax.random.key(1), (2, s + 1), 0, cfg.vocab)

    from repro.models.transformer import forward
    full_logits, _ = forward(params, cfg, model.ctx, tokens=toks)

    _, cache = jax.jit(model.prefill_step)(params, {"tokens": toks[:, :s]})
    # serve_step writes at index s: one more KV slot
    lg, _ = jax.jit(model.serve_step)(params, _grow(cache, 1),
                                      {"tokens": toks[:, s:s + 1]},
                                      jnp.int32(s))
    np.testing.assert_allclose(np.asarray(lg[:, 0]),
                               np.asarray(full_logits[:, -1]),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("arch_id",
                         DECODE_ARCHS + ["llama4-maverick-400b-a17b"])
def test_decode_steps_write_cache_in_place(arch_id, rng_key):
    """Four successive donated serve_steps after a prefill, on a cache
    with spare slots: each step's logits equal the full forward's at its
    position, and the returned cache is the prefill's with each step's
    K/V row written at its position (equal to the full forward's), every
    later slot still zero.  llama4-maverick is the interleaved MoE
    (``moe_every`` 2: two caches a group)."""
    cfg, model, params = _decode_model(arch_id, rng_key)
    s, n, spare = 12, 4, 3
    toks = jax.random.randint(jax.random.key(2), (2, s + n), 0, cfg.vocab)

    from repro.models.transformer import forward
    full_logits, full_cache = forward(params, cfg, model.ctx, tokens=toks,
                                      want_cache=True)

    _, pcache = jax.jit(model.prefill_step)(params, {"tokens": toks[:, :s]})
    prefill_kv = {k: np.asarray(v) for k, v in pcache.items()
                  if k in KV_LEAVES}
    cache = _grow(pcache, n + spare)
    serve = jax.jit(model.serve_step, donate_argnums=(1,))
    for i in range(n):
        pos = s + i
        lg, cache = serve(params, cache, {"tokens": toks[:, pos:pos + 1]},
                          jnp.int32(pos))
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(full_logits[:, pos]),
                                   atol=2e-3, rtol=2e-3)

    for name, got in cache.items():
        got = np.asarray(got)
        want = np.asarray(full_cache[name])
        if name not in KV_LEAVES:           # SSM states: the last step's
            np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)
            continue
        assert got.shape[-2] == s + n + spare
        np.testing.assert_array_equal(got[..., :s, :], prefill_kv[name])
        np.testing.assert_allclose(got[..., s:s + n, :],
                                   want[..., s:s + n, :],
                                   atol=2e-3, rtol=2e-3)
        assert not np.any(got[..., s + n:, :])


@pytest.mark.parametrize("arch_id", ["phi4-mini-3.8b", "zamba2-2.7b",
                                     "llama4-maverick-400b-a17b"])
def test_decode_scan_emits_no_whole_cache(arch_id):
    """No scan or while loop of the decode step outputs anything with
    the cache's sequence axis: the K/V stacks are read in place inside
    the layer scan and only the new rows leave it."""
    cfg = get_config(arch_id).reduced()
    model = make_model(cfg)
    S = 37                               # no other dimension is 37
    shape = ShapeConfig("serve", S, 2, "decode")
    jaxpr = jax.make_jaxpr(model.serve_step)(
        model.param_shapes(), model.cache_specs(shape),
        {"tokens": jax.ShapeDtypeStruct((2, 1), jnp.int32)},
        jax.ShapeDtypeStruct((), jnp.int32))

    def loops(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name in ("scan", "while"):
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from loops(sub)

    found = list(loops(jaxpr.jaxpr))
    assert found
    for eqn in found:
        for v in eqn.outvars:
            assert S not in v.aval.shape, (eqn.primitive.name, v.aval)
    out_cache = jax.tree_util.tree_leaves(jaxpr.out_avals[1:])
    assert any(S in a.shape for a in out_cache)


def test_param_counts_plausible():
    """Config param formula vs actual init sizes (within 1%)."""
    for arch_id in ("llama3.2-3b", "qwen3-moe-30b-a3b", "mamba2-2.7b"):
        cfg = get_config(arch_id)
        model = make_model(cfg)
        shapes = jax.tree_util.tree_leaves(model.param_shapes())
        actual = sum(int(np.prod(s.shape)) for s in shapes)
        approx = cfg.n_params()
        assert abs(actual - approx) / actual < 0.02, \
            (arch_id, actual, approx)


def test_reported_scale_matches_billing_name():
    """Sanity: param counts are in the ballpark the names claim."""
    expect = {"llama3.2-3b": (2.5e9, 4.5e9),
              "phi3-medium-14b": (12e9, 16e9),
              "nemotron-4-15b": (13e9, 18e9),
              "qwen2-vl-72b": (65e9, 80e9),
              "llama4-maverick-400b-a17b": (350e9, 450e9),
              "qwen3-moe-30b-a3b": (25e9, 35e9),
              "mamba2-2.7b": (2.2e9, 3.2e9),
              "zamba2-2.7b": (2.2e9, 3.4e9)}
    for arch_id, (lo, hi) in expect.items():
        n = get_config(arch_id).n_params()
        assert lo <= n <= hi, (arch_id, n)
