"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.merge_runs.kernel import merge_runs_pallas
from repro.kernels.merge_runs.ref import merge_runs_ref
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
from repro.kernels.ssd_scan.ref import ssd_scan_ref, ssd_reference_sequential


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize(
    "b,s,h,kh,d,bq,bk",
    [
        (1, 128, 4, 2, 32, 64, 64),
        (2, 256, 8, 2, 64, 128, 128),
        (1, 256, 4, 4, 32, 64, 128),   # MHA
        (1, 512, 2, 1, 64, 128, 256),  # MQA, rectangular blocks
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, s, h, kh, d, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(b * s + h), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kh, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kh, d), dtype)
    out = flash_attention_pallas(q, k, v, block_q=bq, block_k=bk, interpret=True)
    ref = flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_attention_sliding_window():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 32))
    k = jax.random.normal(ks[1], (2, 128, 2, 32))
    v = jax.random.normal(ks[2], (2, 128, 2, 32))
    out = flash_attention_pallas(q, k, v, block_q=64, block_k=64, window=32, interpret=True)
    ref = flash_attention_ref(q, k, v, window=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_attention_is_causal():
    """Future tokens must not affect earlier outputs: perturb tail, check head."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    out1 = flash_attention_pallas(q, k, v, block_q=64, block_k=64, interpret=True)
    k2 = k.at[:, 100:].set(99.0)
    v2 = v.at[:, 100:].set(-99.0)
    out2 = flash_attention_pallas(q, k2, v2, block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out1[:, :100]), np.asarray(out2[:, :100]), atol=1e-6)


# ---------------------------------------------------------------- ssd scan
@pytest.mark.parametrize(
    "b,s,h,p,g,n,L",
    [
        (2, 64, 4, 16, 1, 16, 16),
        (1, 128, 4, 32, 2, 32, 32),
        (2, 256, 8, 64, 1, 64, 64),
        (1, 64, 2, 8, 1, 8, 64),  # single chunk
    ],
)
def test_ssd_scan_sweep(b, s, h, p, g, n, L):
    ks = jax.random.split(jax.random.PRNGKey(s + h), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.5
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bm = jax.random.normal(ks[3], (b, s, g, n)) * 0.5
    cm = jax.random.normal(ks[4], (b, s, g, n)) * 0.5
    y_pl, s_pl = ssd_scan_pallas(x, dt, a, bm, cm, chunk=L, interpret=True)
    y_ref, s_ref = ssd_scan_ref(x, dt, a, bm, cm, chunk=L)
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s_pl), np.asarray(s_ref), atol=2e-4, rtol=2e-4)


def test_ssd_chunked_ref_matches_sequential():
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    b, s, h, p, g, n = 2, 48, 4, 8, 2, 4
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.5
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bm = jax.random.normal(ks[3], (b, s, g, n)) * 0.5
    cm = jax.random.normal(ks[4], (b, s, g, n)) * 0.5
    y1, s1 = ssd_scan_ref(x, dt, a, bm, cm, chunk=16)
    y2, s2 = ssd_reference_sequential(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)


def test_ssd_initial_state_continuation():
    """Splitting a sequence in half and carrying state == one pass."""
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    b, s, h, p, g, n = 1, 64, 2, 8, 1, 8
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.5
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bm = jax.random.normal(ks[3], (b, s, g, n)) * 0.5
    cm = jax.random.normal(ks[4], (b, s, g, n)) * 0.5
    y_full, s_full = ssd_scan_ref(x, dt, a, bm, cm, chunk=16)
    half = s // 2
    y1, s1 = ssd_scan_ref(x[:, :half], dt[:, :half], a, bm[:, :half], cm[:, :half], chunk=16)
    y2, s2 = ssd_scan_ref(
        x[:, half:], dt[:, half:], a, bm[:, half:], cm[:, half:], chunk=16, initial_state=s1
    )
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(y_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), atol=1e-4)


# --------------------------------------------------------------- merge runs
@pytest.mark.parametrize("g,t", [(8, 64), (16, 128), (8, 256), (32, 32), (1, 512)])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_merge_runs_sweep(g, t, dtype):
    rng = np.random.default_rng(g * t)
    if dtype == np.int32:
        ak = np.sort(rng.integers(0, 1 << 30, (g, t)).astype(dtype), axis=1)
        bk = np.sort(rng.integers(0, 1 << 30, (g, t)).astype(dtype), axis=1)
    else:
        ak = np.sort(rng.standard_normal((g, t)).astype(dtype), axis=1)
        bk = np.sort(rng.standard_normal((g, t)).astype(dtype), axis=1)
    av = rng.integers(0, 1 << 30, (g, t)).astype(np.int32)
    bv = rng.integers(0, 1 << 30, (g, t)).astype(np.int32)
    ok, ov = merge_runs_pallas(jnp.array(ak), jnp.array(bk), jnp.array(av), jnp.array(bv), interpret=True)
    rk, rv = merge_runs_ref(jnp.array(ak), jnp.array(bk), jnp.array(av), jnp.array(bv))
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(rk))
    got = sorted(zip(np.asarray(ok).ravel().tolist(), np.asarray(ov).ravel().tolist()))
    exp = sorted(zip(np.asarray(rk).ravel().tolist(), np.asarray(rv).ravel().tolist()))
    assert got == exp


def test_merge_runs_with_duplicates():
    ak = np.array([[1, 1, 2, 2, 3, 3, 4, 4]], np.int32)
    bk = np.array([[1, 2, 2, 3, 3, 3, 5, 9]], np.int32)
    av = np.arange(8, dtype=np.int32)[None]
    bv = (np.arange(8, dtype=np.int32) + 100)[None]
    ok, _ = merge_runs_pallas(jnp.array(ak), jnp.array(bk), jnp.array(av), jnp.array(bv), interpret=True)
    assert np.array_equal(np.asarray(ok)[0], np.sort(np.concatenate([ak[0], bk[0]])))

