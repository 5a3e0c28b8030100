"""Per-kernel allclose vs pure-jnp oracle: shape & dtype sweeps + property
tests (interpret=True executes the Pallas body on CPU). Property tests use
hypothesis when installed, else a fixed-seed parametrized fallback
(tests/_hyp_compat.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp_compat import given_or_params

from repro.kernels.collapsed_row import (
    collapsed_row_flip,
    collapsed_row_flip_fast,
    collapsed_row_flip_ref,
)
from repro.kernels.feature_stats import feature_stats, feature_stats_ref
from repro.kernels.gaussian_sse import gaussian_sse, gaussian_sse_ref
from repro.kernels.gibbs_flip import gibbs_flip_core, gibbs_flip_ref

SHAPES = [(16, 8, 4), (100, 36, 16), (257, 64, 8), (64, 128, 32), (33, 20, 5)]


def _inputs(N, D, K, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.standard_normal((N, D)), dtype)
    Z = jnp.asarray((rng.random((N, K)) < 0.3), dtype)
    A = jnp.asarray(rng.standard_normal((K, D)), dtype)
    act = jnp.asarray((rng.random(K) < 0.8), dtype)
    return X, Z, A, act, rng


@pytest.mark.parametrize("N,D,K", SHAPES)
@pytest.mark.parametrize("block_n", [32, 128])
def test_gibbs_flip_matches_ref(N, D, K, block_n):
    X, Z, A, act, rng = _inputs(N, D, K)
    lpi = jnp.asarray(rng.standard_normal(K), jnp.float32)
    u = jnp.asarray(rng.standard_normal((N, K)) * 2, jnp.float32)
    inv2s2 = jnp.float32(0.5)
    got = gibbs_flip_core(X, Z, A, lpi, act, u, inv2s2, block_n=block_n)
    want = gibbs_flip_ref(X, Z, A, lpi, act, u, inv2s2)
    assert jnp.all(got == want), f"mismatch at {(N, D, K, block_n)}"


@pytest.mark.parametrize("N,D,K", SHAPES)
def test_feature_stats_matches_ref(N, D, K):
    X, Z, _, _, _ = _inputs(N, D, K)
    ztz, ztx, m = feature_stats(X, Z, block_n=64)
    ztz_r, ztx_r, m_r = feature_stats_ref(X, Z)
    np.testing.assert_allclose(ztz, ztz_r, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ztx, ztx_r, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(m, m_r)


@pytest.mark.parametrize("N,D,K", SHAPES)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_gaussian_sse_matches_ref(N, D, K, dtype):
    X, Z, A, act, _ = _inputs(N, D, K, dtype=dtype)
    got = gaussian_sse(X, Z, A, act, block_n=64)
    want = gaussian_sse_ref(X, Z, A, act)
    rtol = 1e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(float(got), float(want), rtol=rtol)


# ---------------------------------------------------------------------------
# collapsed_row: the K-sequential collapsed bit-flip recurrence
# ---------------------------------------------------------------------------


def _collapsed_row_inputs(K, D, seed=0, frac_active=1.0):
    rng = np.random.default_rng(seed)
    act = (rng.random(K) < frac_active).astype(np.float32)
    if act.sum() == 0:
        act[0] = 1.0
    Zb = ((rng.random((5 * K, K)) < 0.3) * act).astype(np.float32)
    W = Zb.T @ Zb + 0.7 * np.diag(act) + np.diag(1 - act)
    M = (np.linalg.inv(W) * np.outer(act, act)).astype(np.float32)
    ZtX = (Zb.T @ rng.standard_normal((5 * K, D))).astype(np.float32)
    H = (M @ ZtX).astype(np.float32)
    x = rng.standard_normal(D).astype(np.float32)
    z = ((rng.random(K) < 0.4) * act).astype(np.float32)
    v = (M @ z).astype(np.float32)
    q = np.float32(z @ v)
    mean = (z @ H).astype(np.float32)
    u = (rng.standard_normal(K) * 2).astype(np.float32)
    mm = Zb.sum(0).astype(np.float32)
    args = [jnp.asarray(a) for a in (M, H, x, z, v, q, mean, u, mm, act)]
    return args + [jnp.float32(8 * K), jnp.float32(0.5)]


@pytest.mark.parametrize("K,D", [(8, 16), (16, 36), (64, 64), (5, 7),
                                 (12, 128)])
def test_collapsed_row_packed_with_carried_g_matches_ref(K, D):
    """The flip the tail's scan runs: the packed flavor handed the carried
    G = H Hᵀ, as ``_packed_scan`` hands it, against the jnp oracle —
    decisions exactly, the carried quadratics to the tolerance of
    ``test_collapsed_row_fast_matches_ref_under_padding``."""
    args = _collapsed_row_inputs(K, D, seed=K + D)
    H = args[1]
    G = jnp.dot(H, H.T, precision="highest")
    zr, vr, qr, mr = collapsed_row_flip_ref(*args)
    zp, vp, qp, mp = collapsed_row_flip(*args, flavor="packed", G=G)
    np.testing.assert_array_equal(np.asarray(zr), np.asarray(zp))
    np.testing.assert_allclose(np.asarray(vr), np.asarray(vp),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(float(qr), float(qp), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(mr), np.asarray(mp),
                               rtol=1e-3, atol=1e-3)


@given_or_params(max_examples=20, k=(2, 24), d=(2, 48), seed=(0, 10_000))
def test_collapsed_row_fast_matches_ref_under_padding(k, d, seed):
    """The packed-active rss/rH flavor must reproduce the oracle's
    decisions and carried quadratics (different float path, so the
    continuous outputs get a tolerance; decisions are compared exactly
    on this fixed-seed grid)."""
    rng = np.random.default_rng(seed)
    args = _collapsed_row_inputs(k, d, seed=seed,
                                 frac_active=float(rng.uniform(0.3, 1.0)))
    zr, vr, qr, mr = collapsed_row_flip_ref(*args)
    zf, vf, qf, mf = collapsed_row_flip_fast(*args)
    np.testing.assert_array_equal(np.asarray(zr), np.asarray(zf))
    np.testing.assert_allclose(np.asarray(vr), np.asarray(vf),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(float(qr), float(qf), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(mr), np.asarray(mf),
                               rtol=1e-3, atol=1e-3)
    # inactive columns must never flip
    inact = np.asarray(args[9]) < 0.5
    np.testing.assert_array_equal(np.asarray(zf)[inact],
                                  np.asarray(args[3])[inact])


# ---------------------------------------------------------------------------
# property-based: invariants of the Gibbs-flip kernel
# ---------------------------------------------------------------------------


@given_or_params(max_examples=20, n=(5, 70), d=(2, 40), k=(1, 12),
                 seed=(0, 10_000))
def test_gibbs_flip_property_binary_and_active_respected(n, d, k, seed):
    X, Z, A, act, rng = _inputs(n, d, k, seed=seed)
    lpi = jnp.asarray(rng.standard_normal(k), jnp.float32)
    u = jnp.asarray(rng.standard_normal((n, k)) * 2, jnp.float32)
    out = gibbs_flip_core(X, Z, A, lpi, act, u, jnp.float32(0.5), block_n=32)
    out_np = np.asarray(out)
    # output is binary
    assert set(np.unique(out_np)).issubset({0.0, 1.0})
    # inactive columns unchanged
    inactive = np.asarray(act) < 0.5
    np.testing.assert_array_equal(out_np[:, inactive], np.asarray(Z)[:, inactive])
    # kernel == oracle everywhere (the strongest property)
    want = np.asarray(gibbs_flip_ref(X, Z, A, lpi, act, u, jnp.float32(0.5)))
    np.testing.assert_array_equal(out_np, want)


@given_or_params(max_examples=20, n=(5, 60), d=(2, 30), k=(1, 10),
                 seed=(0, 10_000))
def test_feature_stats_property_psd_and_counts(n, d, k, seed):
    X, Z, _, _, _ = _inputs(n, d, k, seed=seed)
    ztz, ztx, m = feature_stats(X, Z, block_n=32)
    # ZtZ is PSD with diagonal = column counts = m
    np.testing.assert_allclose(np.diag(np.asarray(ztz)), np.asarray(m))
    evals = np.linalg.eigvalsh(np.asarray(ztz))
    assert evals.min() > -1e-4
    # m bounded by N
    assert np.all(np.asarray(m) <= n)


@given_or_params(max_examples=20, n=(5, 60), d=(2, 30), k=(1, 10),
                 seed=(0, 10_000))
def test_gaussian_sse_property_nonneg_and_zero_residual(n, d, k, seed):
    X, Z, A, act, _ = _inputs(n, d, k, seed=seed)
    s = gaussian_sse(X, Z, A, act, block_n=32)
    assert float(s) >= 0
    # exact-zero residual case
    X2 = (Z * act[None, :]) @ A
    s2 = gaussian_sse(X2, Z, A, act, block_n=32)
    assert float(s2) < 1e-3 * max(1.0, float(jnp.sum(X2 * X2)))
