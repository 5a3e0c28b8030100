"""Backend equivalence + drift-monitor tests for the fast collapsed sampler.

The ``backend="fast"`` row step carries (Lt, M, H) across the row scan via
rank-one Cholesky up/downdates + Sherman–Morrison instead of refactorizing
per row (DESIGN.md §12), and — under ``k_live_buckets="on"`` (default) —
runs that carry PACKED to the live K⁺ bucket with G = HHᵀ carried
rank-one (DESIGN.md §14). These tests certify the speedup is not bought
with approximation:

* full sweeps with the fast (and pallas) backend — packed and unpacked —
  reproduce the O(K^3) oracle's accept decisions on a fixed seed grid —
  same PRNG keys, same chain. A tiny mismatch budget (<=2 bits per run)
  absorbs measure-zero likelihood-boundary events where the two float
  paths may legitimately round an accept differently; a broken carry
  diverges by hundreds of bits within a sweep.
* forced bucket-boundary crossings (births overflowing the block
  mid-sweep -> repack up + resume; post-burn-in deaths -> repack down)
  stay on the oracle's trajectory: bucket repack is a pure permutation +
  refresh.
* the drift monitor actually triggers refreshes when told to distrust the
  carry (tight tolerance) and stays quiet when the carry is healthy, and
  a monitor-repaired chain still matches the oracle.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ibp import IBPHypers, collapsed_sweep, init_state
from repro.core.ibp.collapsed import PROBE_EVERY, collapsed_row_scan
from repro.core.ibp import math as ibm
from repro.data import cambridge_data

MISMATCH_BUDGET = 2  # bits per run; boundary events, not drift


@pytest.fixture(scope="module")
def data():
    X, _, _ = cambridge_data(N=100, sigma_n=0.4, seed=3)
    return jnp.asarray(X)


def _run(X, backend, refresh, sweeps, seed, k_live="on", seg_log=None,
         K_max=16, K_init=2, alpha=3.0, st=None):
    hyp = IBPHypers()
    if st is None:
        st = init_state(jax.random.key(seed), X.shape[0], X.shape[1],
                        K_max=K_max, K_init=K_init, alpha=alpha)
    for _ in range(sweeps):
        st = collapsed_sweep(st, X, hyp, backend=backend,
                             refresh_every=refresh,
                             k_live_buckets=k_live, seg_log=seg_log)
    return st


def _mismatch(a, b):
    return int(jnp.sum(a.Z * a.active[None, :] != b.Z * b.active[None, :]))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("refresh", [8, 32])
def test_fast_sweep_matches_oracle_sweep(data, seed, refresh):
    a = _run(data, "ref", refresh, sweeps=5, seed=seed)
    b = _run(data, "fast", refresh, sweeps=5, seed=seed)
    mism = _mismatch(a, b)
    assert mism <= MISMATCH_BUDGET, f"{mism} bits diverged (seed={seed})"
    assert np.isclose(float(a.sigma_x), float(b.sigma_x), rtol=1e-3)
    assert np.isclose(float(a.alpha), float(b.alpha), rtol=1e-3)
    assert int(a.active.sum()) == int(b.active.sum())


@pytest.mark.parametrize("seed", [0, 2])
def test_unpacked_fast_sweep_matches_oracle_sweep(data, seed):
    """k_live_buckets="off" (the pre-packing carry) stays certified too."""
    a = _run(data, "ref", 8, sweeps=5, seed=seed)
    b = _run(data, "fast", 8, sweeps=5, seed=seed, k_live="off")
    assert _mismatch(a, b) <= MISMATCH_BUDGET
    assert np.isclose(float(a.sigma_x), float(b.sigma_x), rtol=1e-3)


def test_packed_sweep_bitwise_across_bucket_growth():
    """Cold start on rich data with a high alpha: births overflow the
    8-bucket MID-SWEEP, forcing repack-up + resume — decisions must stay
    on the oracle's trajectory through every crossing."""
    rng = np.random.default_rng(0)
    Zt = (rng.random((120, 12)) < 0.4).astype(np.float32)
    At = rng.standard_normal((12, 24)).astype(np.float32) * 1.5
    X = jnp.asarray(Zt @ At + 0.3 * rng.standard_normal(
        (120, 24)).astype(np.float32))
    a = _run(X, "ref", 8, sweeps=4, seed=0, K_max=32, K_init=1, alpha=8.0)
    seg = []
    b = _run(X, "fast", 8, sweeps=4, seed=0, K_max=32, K_init=1, alpha=8.0,
             seg_log=seg)
    assert _mismatch(a, b) <= MISMATCH_BUDGET, seg
    buckets = {s[0] for s in seg}
    assert len(buckets) >= 2, f"no bucket crossing exercised: {seg}"
    assert any(row > 0 for _, row in seg), \
        f"no MID-sweep overflow repack exercised: {seg}"
    assert int(a.active.sum()) == int(b.active.sum())


def test_packed_sweep_bitwise_across_bucket_shrink(data):
    """Post-burn-in deaths drop occupancy below the bucket: the next
    sweep repacks DOWN (reusing its boundary refactorization) and must
    match the oracle from the same state."""
    hyp = IBPHypers()
    st = init_state(jax.random.key(2), data.shape[0], data.shape[1],
                    K_max=32, K_init=12)
    seg = []
    for _ in range(2):
        st = collapsed_sweep(st, data, hyp, backend="fast",
                             refresh_every=8, seg_log=seg)
    assert seg[0][0] == 16  # 12 live + headroom -> the 16 bucket
    # deaths after burn-in: keep only the first 3 live columns (the
    # driver-level shrink scenario), then compare ref vs packed from the
    # SAME reduced state
    act = np.asarray(st.active)
    keep = np.zeros_like(act)
    keep[np.flatnonzero(act > 0.5)[:3]] = 1.0
    keep_j = jnp.asarray(keep)
    st2 = dataclasses.replace(
        st, Z=st.Z * keep_j[None, :], active=st.active * keep_j)
    a = _run(data, "ref", 8, sweeps=2, seed=0, st=st2)
    seg2 = []
    b = _run(data, "fast", 8, sweeps=2, seed=0, seg_log=seg2, st=st2)
    assert seg2[0][0] == 8, f"bucket did not shrink: {seg2}"
    assert _mismatch(a, b) <= MISMATCH_BUDGET
    assert int(a.active.sum()) == int(b.active.sum())


def test_scan_pack_matches_ref_decisions(data):
    """The in-jit packed entry (pack=True — the hybrid tail's route, full
    width + carried G) reproduces the oracle scan's decisions."""
    N = data.shape[0]
    args = _scan_kwargs(data)
    Zr, ar, *_ = collapsed_row_scan(*args, N=float(N), backend="ref")
    Zp, ap, *_ = collapsed_row_scan(*args, N=float(N), backend="fast",
                                    pack=True)
    mism = int(jnp.sum(Zr * ar[None, :] != Zp * ap[None, :]))
    assert mism <= MISMATCH_BUDGET, mism


def _scan_kwargs(X, seed=0, K_max=12):
    N, D = X.shape
    rng_key = jax.random.key(seed)
    st = init_state(rng_key, N, D, K_max=K_max, K_init=3)
    Z, active = st.Z, st.active
    m = jnp.sum(Z * active[None, :], axis=0)
    ZtZ = (Z.T @ Z) * ibm.mask_outer(active)
    ZtX = (Z.T @ X) * active[:, None]
    return (Z, active, ZtZ, ZtX, m, X, jax.random.fold_in(rng_key, 7),
            st.alpha, st.sigma_x, st.sigma_a)


def test_ref_backend_reports_zero_refreshes(data):
    args = _scan_kwargs(data)
    *_, n_refresh, _ = collapsed_row_scan(*args, N=float(data.shape[0]),
                                          backend="ref")
    assert int(n_refresh) == 0


def test_drift_monitor_triggers_refresh_when_distrusted(data):
    """With a refresh cadence longer than the scan and an impossible drift
    tolerance, every probed row must force a monitor refresh; with a sane
    tolerance the cadence alone accounts for (almost) all refreshes."""
    N = data.shape[0]
    args = _scan_kwargs(data)

    # cadence-only baseline: huge tolerance, cadence 25 -> ~N/25 refreshes
    *_, n_cadence, _ = collapsed_row_scan(
        *args, N=float(N), backend="fast", refresh_every=25, drift_tol=1e9)
    assert int(n_cadence) == N // 25, int(n_cadence)

    # distrust the carry completely: every probed row triggers
    *_, n_forced, _ = collapsed_row_scan(
        *args, N=float(N), backend="fast", refresh_every=10**6,
        drift_tol=0.0)
    assert int(n_forced) >= N // PROBE_EVERY, int(n_forced)

    # healthy carry, no cadence: the monitor stays quiet over a short scan
    *_, n_quiet, _ = collapsed_row_scan(
        *args, N=float(N), backend="fast", refresh_every=10**6,
        drift_tol=1e-2)
    assert int(n_quiet) <= 2, int(n_quiet)


def test_drift_monitor_works_under_pack(data):
    """The packed scan carries the same probe monitor (extended with the
    G-consistency residual): distrusting the carry forces refreshes at
    the probe cadence; a healthy packed carry stays quiet."""
    N = data.shape[0]
    args = _scan_kwargs(data)
    *_, n_forced, _ = collapsed_row_scan(
        *args, N=float(N), backend="fast", refresh_every=10**6,
        drift_tol=0.0, pack=True)
    assert int(n_forced) >= N // PROBE_EVERY, int(n_forced)
    *_, n_quiet, _ = collapsed_row_scan(
        *args, N=float(N), backend="fast", refresh_every=10**6,
        drift_tol=1e-2, pack=True)
    assert int(n_quiet) <= 2, int(n_quiet)


def test_monitor_repaired_chain_still_matches_oracle(data):
    """Forcing monitor refreshes must leave the chain on the oracle's
    trajectory (a refresh is exact, so MORE refreshes can only help)."""
    hyp = IBPHypers()
    a = _run(data, "ref", 8, sweeps=3, seed=5)
    st = init_state(jax.random.key(5), data.shape[0], data.shape[1],
                    K_max=16, K_init=2)
    for _ in range(3):
        st = collapsed_sweep(st, data, hyp, backend="fast", refresh_every=2)
    mism = int(jnp.sum(a.Z * a.active[None, :] != st.Z * st.active[None, :]))
    assert mism <= MISMATCH_BUDGET, mism


def test_packed_scan_uniform_chunking_is_bitwise(data):
    """The hoisted per-row uniform buffer is generated block-wise
    (U_CHUNK_ROWS at a time) for large serial N — the key chain is
    positional, so every chunk size must reproduce the identical
    bitstream, hence identical decisions AND identical carry-out key."""
    from repro.core.ibp.collapsed import _packed_scan

    N = data.shape[0]
    args = _scan_kwargs(data, seed=3)

    def norm(leaf):
        if hasattr(leaf, "dtype") and jax.dtypes.issubdtype(
                leaf.dtype, jax.dtypes.prng_key):
            return np.asarray(jax.random.key_data(leaf))
        return np.asarray(leaf)

    outs = {}
    for chunk in (3, 16, 4096):
        out = _packed_scan(*args, 0, N=float(N), birth="gibbs", B=8,
                           refresh_every=64, u_chunk_rows=chunk)
        outs[chunk] = [norm(x) for x in out]
    for chunk in (16, 4096):
        for a, b in zip(outs[3], outs[chunk]):
            np.testing.assert_array_equal(a, b)


def test_packed_resume_bitwise_at_chunk_boundary():
    """Satellite regression: the overflow-repack resume re-reads its
    uniforms POSITIONALLY — when the overflow row lands exactly on a
    u_chunk_rows boundary (the resumed row's draw sits at the first slot
    of a refilled block, and the overflowing attempt itself triggered
    the refill), the chunked re-read must be bitwise identical to the
    unchunked hoist. The chunk sizes are derived from the actual
    overflow rows so each resume start IS a block boundary."""
    from repro.core.ibp.collapsed import (PACK_HEADROOM, _packed_scan,
                                          _sweep_stats)

    rng = np.random.default_rng(0)
    Zt = (rng.random((120, 12)) < 0.4).astype(np.float32)
    At = rng.standard_normal((12, 24)).astype(np.float32) * 1.5
    X = jnp.asarray(Zt @ At
                    + 0.3 * rng.standard_normal((120, 24)).astype(np.float32))
    N = 120
    st = init_state(jax.random.key(0), N, 24, K_max=32, K_init=1, alpha=8.0)
    buckets = ibm.live_buckets(32)

    def sweep(u_chunk):
        m, ZtZ, ZtX, kp = _sweep_stats(st.Z, st.active, X)
        Z, active = st.Z, st.active
        key = jax.random.fold_in(st.key, 1)
        row, segs = 0, []
        kp = int(kp)
        while row < N:
            B = ibm.pick_bucket(buckets, kp, PACK_HEADROOM)
            segs.append((B, row))
            Z, active, ZtZ, ZtX, m, _, _, key, ovf_row = _packed_scan(
                Z, active, ZtZ, ZtX, m, X, key, st.alpha, st.sigma_x,
                st.sigma_a, row, N=float(N), birth="gibbs", B=B,
                refresh_every=8, u_chunk_rows=u_chunk)
            ovf, kp = map(int, jax.device_get((ovf_row, jnp.sum(active))))
            row = N if ovf < 0 else ovf
        return segs, (Z, active, ZtZ, ZtX, m)

    segs_ref, out_ref = sweep(4096)          # one block covers every segment
    starts = [r for _, r in segs_ref if r > 0]
    assert starts, "setup no longer overflows mid-sweep; rechoose data"
    # chunk sizes that put each resume start exactly on a block boundary
    chunks = sorted({r for r in starts}
                    | {b - a for a, b in zip(starts, starts[1:]) if b > a})
    for c in chunks:
        segs_c, out_c = sweep(c)
        assert segs_c == segs_ref, (c, segs_c, segs_ref)
        for name, x, y in zip(("Z", "active", "ZtZ", "ZtX", "m"),
                              out_ref, out_c):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y),
                err_msg=f"{name} diverged at u_chunk_rows={c}")
