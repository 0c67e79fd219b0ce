"""Pallas kernel allclose sweeps against the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import attention_op, ssd_scan_op
from repro.kernels.ref import ref_attention, ref_ssd
from repro.models.mamba2 import ssd_chunked


def _qkv(key, b, h, kvh, sq, skv, d, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, sq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, kvh, skv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, kvh, skv, d), jnp.float32).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("b,h,kvh,s,d", [
    (1, 4, 4, 128, 64),     # MHA
    (2, 8, 2, 256, 64),     # GQA 4:1
    (1, 6, 2, 128, 128),    # GQA 3:1, wide head
    (1, 4, 1, 384, 32),     # MQA, non-square block count
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, h, kvh, s, d, dtype, rng_key):
    q, k, v = _qkv(rng_key, b, h, kvh, s, s, d, dtype)
    out = flash_attention(q, k, v, interpret=True)
    ref = ref_attention(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window, rng_key):
    q, k, v = _qkv(rng_key, 1, 4, 2, 256, 256, 64, jnp.float32)
    out = flash_attention(q, k, v, window=window, interpret=True)
    ref = ref_attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_blocks(rng_key):
    """Block-shape sweep: result must be block-shape independent."""
    q, k, v = _qkv(rng_key, 1, 2, 2, 256, 256, 64, jnp.float32)
    ref = ref_attention(q, k, v)
    for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]:
        out = flash_attention(q, k, v, block_q=bq, block_k=bk,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def _ssd_inputs(key, b, s, H, P, G, N, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s, H, P), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, G, N), jnp.float32).astype(dtype)
    C = jax.random.normal(ks[4], (b, s, G, N), jnp.float32).astype(dtype)
    return x, dt, A, B, C


@pytest.mark.parametrize("b,s,H,P,G,N,chunk", [
    (1, 64, 2, 16, 1, 8, 16),
    (2, 128, 4, 32, 2, 16, 32),
    (1, 96, 4, 16, 4, 8, 16),   # non-power-of-two chunk count
    (1, 64, 8, 64, 1, 32, 64),  # single-group, wide head
])
def test_ssd_chunked_vs_naive(b, s, H, P, G, N, chunk, rng_key):
    x, dt, A, B, C = _ssd_inputs(rng_key, b, s, H, P, G, N)
    y_ref, h_ref = ref_ssd(x, dt, A, B, C, return_state=True)
    y, h = ssd_chunked(x, dt, A, B, C, chunk, return_state=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("chunk", [16, 32])
def test_ssd_pallas_vs_naive(chunk, rng_key):
    x, dt, A, B, C = _ssd_inputs(rng_key, 2, 64, 4, 16, 2, 8)
    y_ref = ref_ssd(x, dt, A, B, C)
    y = ssd_scan_op(x, dt, A, B, C, chunk, use_pallas="interpret")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)


def test_ssd_initial_state_chaining(rng_key):
    """Running two halves with state carry == running the whole seq."""
    x, dt, A, B, C = _ssd_inputs(rng_key, 1, 64, 2, 16, 1, 8)
    y_full, h_full = ssd_chunked(x, dt, A, B, C, 16, return_state=True)
    y1, h1 = ssd_chunked(x[:, :32], dt[:, :32], A, B[:, :32], C[:, :32],
                         16, return_state=True)
    y2, h2 = ssd_chunked(x[:, 32:], dt[:, 32:], A, B[:, 32:], C[:, 32:],
                         16, initial_state=h1, return_state=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], axis=1)),
                               np.asarray(y_full), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               atol=1e-4, rtol=1e-4)


def test_ops_dispatch_xla_fallback(rng_key):
    q, k, v = _qkv(rng_key, 1, 2, 2, 64, 64, 32, jnp.float32)
    out = attention_op(q, k, v, use_pallas="auto")   # CPU -> XLA ref
    ref = ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


@pytest.mark.parametrize("b,h,kvh,S,d,block_k", [
    (2, 8, 2, 256, 64, 128),
    (1, 4, 4, 512, 128, 128),
    (3, 6, 2, 256, 32, 64),
])
def test_flash_decode_vs_ref(b, h, kvh, S, d, block_k, rng_key):
    """Flash-decode == full attention at the final position, with
    per-row context lengths masking the cache tail."""
    from repro.kernels.flash_attention import flash_decode
    ks = jax.random.split(rng_key, 4)
    q = jax.random.normal(ks[0], (b, h, 1, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, kvh, S, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, kvh, S, d), jnp.float32)
    lengths = jax.random.randint(ks[3], (b,), S // 4, S + 1)
    out = flash_decode(q, k, v, lengths, block_k=block_k, interpret=True)
    # reference: mask invalid positions then ordinary attention
    for i in range(b):
        L = int(lengths[i])
        ref = ref_attention(q[i:i + 1], k[i:i + 1, :, :L],
                            v[i:i + 1, :, :L], causal=False)
        np.testing.assert_allclose(np.asarray(out[i:i + 1]),
                                   np.asarray(ref), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------- #
# batched feasibility scan (kernels/feasibility.py)
# ---------------------------------------------------------------------- #
def _feasibility_case(seed=0, n_req=11, n_vert=300, n_types=5):
    """Random request/vertex tables exercising every clause: type
    mismatch, busy vertices, size floors, 62-bit property masks (both
    int31 halves), and the per-type aggregate check."""
    rng = np.random.default_rng(seed)
    vtype = rng.integers(0, n_types, n_vert, dtype=np.int32)
    vok = rng.integers(0, 2, n_vert, dtype=np.int32)
    vsize = rng.integers(1, 64, n_vert, dtype=np.int32)
    # bits on both sides of the int31 split (bit 40 > 31)
    vmask = (rng.integers(0, 2, n_vert, dtype=np.int64) << 40
             | rng.integers(0, 8, n_vert, dtype=np.int64))
    agg = rng.integers(0, 16, (n_vert, n_types), dtype=np.int32)
    tid = rng.integers(0, n_types, n_req, dtype=np.int32)
    msize = rng.integers(1, 48, n_req, dtype=np.int32)
    rmask = (rng.integers(0, 2, n_req, dtype=np.int64) << 40
             | rng.integers(0, 4, n_req, dtype=np.int64))
    need = rng.integers(0, 12, (n_req, n_types), dtype=np.int32)
    return vtype, vok, vsize, vmask, agg, tid, msize, rmask, need


def _feasibility_numpy(vtype, vok, vsize, vmask, agg,
                       tid, msize, rmask, need):
    m = (vtype[None, :] == tid[:, None]) & (vok[None, :] != 0)
    m &= vsize[None, :] >= msize[:, None]
    m &= (vmask[None, :] & rmask[:, None]) == rmask[:, None]
    m &= (agg[None, :, :] >= need[:, None, :]).all(axis=2)
    return m.astype(np.int32)


@pytest.mark.parametrize("seed,n_req,n_vert", [
    (0, 11, 300),       # ragged: pads both request and vertex blocks
    (1, 8, 256),        # exact block multiples: no padding
    (2, 1, 33),         # single request, tiny vertex count
    (3, 40, 1024),      # deep window
])
def test_batched_feasible_xla_vs_numpy(seed, n_req, n_vert):
    """The scan's device half as the program runs it (``pack_inputs``,
    then ``run_packed``: the kernel, interpreted off the TPU) against
    the numpy oracle."""
    from repro.kernels.feasibility import pack_inputs, run_packed
    case = _feasibility_case(seed, n_req, n_vert)
    want = _feasibility_numpy(*case)
    got = run_packed(pack_inputs(*case))[:n_req, :n_vert]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("seed,n_req,n_vert", [
    (0, 11, 300),
    (1, 8, 256),
    (4, 13, 97),
])
def test_batched_feasible_pallas_interpret_vs_xla(seed, n_req, n_vert):
    """The kernel in interpret mode against the numpy oracle, and the
    program's host half (``flatgraph.batched_candidate_mask``) against
    the same oracle."""
    from repro.core.flatgraph import batched_candidate_mask
    from repro.kernels.feasibility import _feasible_pallas, pack_inputs
    case = _feasibility_case(seed, n_req, n_vert)
    want = _feasibility_numpy(*case)
    out = np.asarray(_feasible_pallas(*pack_inputs(*case), interpret=True))
    np.testing.assert_array_equal(out[:n_req, :n_vert], want)
    vtype, vok = case[:2]
    host = batched_candidate_mask(vtype, vok != 0, *case[2:])
    np.testing.assert_array_equal(host.astype(np.int32), want)
