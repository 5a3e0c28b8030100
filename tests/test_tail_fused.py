"""The fused tail: the hybrid tail's whole row scan as one Pallas kernel
(``kernels/tail_scan``, DESIGN.md §12 "The fused tail").

The kernel runs in interpret mode here and is held against the XLA
scan it replaces on the TPU — ``collapsed_row_scan(birth="mh",
backend="fast", pack=True)``, i.e. ``_packed_scan`` at full width — on
the same rows and the same random numbers. The two compute the same
floats by different reductions, so decisions may differ only at
likelihood-boundary events: the Z_tail bits agree within
``MISMATCH_BUDGET`` and the refresh and saturation counts are equal.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ibp import IBPHypers, SamplerSpec, build_sampler
from repro.core.ibp import collapsed
from repro.core.ibp.collapsed import (PROBE_EVERY, collapsed_row_scan,
                                      fused_tail_scan)
from repro.kernels.tail_scan import ROW_BLOCK

MISMATCH_BUDGET = 2  # bits per run, as tests/test_collapsed_fast.py
D = 36


def _tail_inputs(seed, n, K, start):
    """A tail's statistics and residual: six planted features, of which
    the tail starts holding ``start`` ("empty": none, as after a sync;
    "held": two, one of them emptied so that it dies and is reborn)."""
    rng = np.random.default_rng(seed)
    Zp = (rng.random((n, 6)) < 0.3).astype(np.float32)
    A = 2.0 * rng.standard_normal((6, D)).astype(np.float32)
    R = Zp @ A + 0.5 * rng.standard_normal((n, D)).astype(np.float32)
    Z = np.zeros((n, K), np.float32)
    if start == "held":
        Z[:, :2] = Zp[:, :2]
        Z[: n // 2, 1] = 0.0
        Z[n // 2 + 1:, 1] = 0.0   # a singleton
    Z, R = jnp.asarray(Z), jnp.asarray(R)
    active = (jnp.sum(Z, axis=0) > 0).astype(jnp.float32)
    return (Z, active, Z.T @ Z, Z.T @ R, jnp.sum(Z, axis=0), R,
            jax.random.key(seed), jnp.float32(3.0), jnp.float32(0.5),
            jnp.float32(2.0))


def _scan(args, N, refresh, drift_tol):
    return collapsed_row_scan(
        *args, N=N, birth="mh", backend="fast", refresh_every=refresh,
        drift_tol=drift_tol, pack=True, u_chunk_rows=args[0].shape[0])


def _fused(args, N, refresh, drift_tol):
    # off a TPU the kernel runs in interpret mode
    return fused_tail_scan(*args, N=N, refresh_every=refresh,
                           drift_tol=drift_tol)


def _agree(a, b):
    mism = int(jnp.sum(a[0] != b[0]))
    assert mism <= MISMATCH_BUDGET, f"{mism} Z_tail bits differ"
    assert int(a[5]) == int(b[5]), ("n_refresh", int(a[5]), int(b[5]))
    assert int(a[6]) == int(b[6]), ("n_sat", int(a[6]), int(b[6]))
    if mism == 0:
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
        np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(b[2]))
        np.testing.assert_allclose(np.asarray(a[3]), np.asarray(b[3]),
                                   atol=1e-4)


# (N_p, K_tail, start, refresh_every, drift_tol); N_p 180, K_tail 8 is
# the paper's instance; refresh 8 takes the in-kernel refactorization
# every eighth row and the drift probe every fourth; a negative
# drift_tol makes the monitor force the refactorization on every row;
# K_tail 16 is the first adaptive growth; N_p 300 is not a multiple of
# the row block
CASES = {
    "paper": (180, 8, "empty", 64, 1e-2),
    "paper_held": (180, 8, "held", 64, 1e-2),
    "refresh8": (180, 8, "held", 8, 1e-2),
    "forced_every_row": (180, 8, "held", 64, -1.0),
    "k16": (180, 16, "held", 64, 1e-2),
    "ragged_blocks": (300, 8, "empty", 64, 1e-2),
}


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_scan_matches_xla_scan(case, seed):
    n, K, start, refresh, tol = CASES[case]
    args = _tail_inputs(seed, n, K, start)
    N = 2.0 * n
    a = _scan(args, N, refresh, tol)
    b = _fused(args, N, refresh, tol)
    _agree(a, b)
    # the case exercised what it names
    if case == "refresh8":
        assert int(b[5]) >= n // refresh
    if case == "forced_every_row":
        assert int(b[5]) == n
    if case == "ragged_blocks":
        assert n % ROW_BLOCK != 0 and n > ROW_BLOCK
    if start == "empty":
        assert int(jnp.sum(b[1])) > 0   # births


def test_fused_scan_counts_saturation():
    """A tail too narrow for its births: accepted births vetoed for
    lack of free columns are counted alike."""
    args = list(_tail_inputs(4, 180, 8, "empty"))
    args[7] = jnp.float32(60.0)          # alpha: many proposals
    a = _scan(args, 180.0, 64, 1e-2)
    b = _fused(args, 180.0, 64, 1e-2)
    _agree(a, b)
    assert int(b[6]) > 0


def test_fused_scan_under_vmap():
    """``chains="vmap"`` batches the kernel: the batching rule adds a grid
    axis, and each batch member matches its own unbatched scan."""
    batch = [_tail_inputs(s, 180, 8, st) for s, st in ((5, "empty"),
                                                        (6, "held"))]
    stacked = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *batch)
    out = jax.vmap(lambda *a: _fused(a, 360.0, 8, 1e-2))(*stacked)
    for i, args in enumerate(batch):
        _agree(_scan(args, 360.0, 8, 1e-2),
               jax.tree_util.tree_map(lambda x: x[i], out))


def test_drift_probe_reads_a_perturbed_carry():
    """The kernel's drift probe, outside the kernel, against the monitor's
    formula in float64: it reads a carry that has drifted."""
    from repro.kernels.tail_scan import kernel

    rng = np.random.default_rng(0)
    K, ratio = 8, 4.0
    act = np.array([1, 1, 1, 0, 1, 0, 0, 0], np.float64)
    Z = (rng.random((40, K)) < 0.4) * act
    ZtZ = Z.T @ Z
    z = Z[3]
    H = rng.standard_normal((K, D)) * act[:, None]
    W = ((ZtZ - np.outer(z, z)) + ratio * np.eye(K)) * np.outer(act, act)
    M = np.linalg.pinv(W) + 1e-3 * np.outer(act, act)   # drifted
    G = H @ H.T + 1e-2 * np.outer(act, act)             # drifted
    p = act * (ZtZ @ act - z * (z @ act)) + ratio * act
    want = max(np.max(np.abs(M @ p - act)),
               np.max(np.abs(G @ act - H @ (act @ H)))
               / (1.0 + np.max(np.abs(G))))
    row = lambda v: jnp.asarray(v[None, :], jnp.float32)
    got = kernel._drift(kernel._Consts(K), jnp.asarray(ZtZ, jnp.float32),
                        row(z), row(act), jnp.asarray(M, jnp.float32),
                        jnp.asarray(H, jnp.float32),
                        jnp.asarray(G, jnp.float32), ratio)
    assert got.shape == (1, 1)
    np.testing.assert_allclose(float(got[0, 0]), want, rtol=1e-4)
    assert want > 1e-2   # the monitor's default tolerance trips


def test_tail_path_is_fused_only_on_tpu_for_packed_fast(monkeypatch):
    assert collapsed.tail_path("fast", True) == (
        "fused" if jax.default_backend() == "tpu" else "scan")
    monkeypatch.setattr(collapsed, "on_tpu", lambda: True)
    assert collapsed.tail_path("fast", True) == "fused"
    for backend, pack in (("fast", False), ("ref", True), ("pallas", True)):
        assert collapsed.tail_path(backend, pack) == "scan"


HYBRID = dict(P=5, L=2, K_max=12, K_tail=4, K_init=1, alpha=3.0,
              sigma_x=0.3, sigma_a=3.0, collapsed_backend="fast",
              chol_refresh=8)


@pytest.fixture(scope="module")
def X():
    rng = np.random.default_rng(0)
    Zt = (rng.random((100, 6)) < 0.5).astype(np.float32)
    At = 3.0 * rng.standard_normal((6, D)).astype(np.float32)
    return Zt @ At + 0.3 * rng.standard_normal((100, D)).astype(np.float32)


@pytest.mark.parametrize("chains", ["none", "vmap"])
def test_hybrid_step_with_fused_tail_on_data_vmap(X, chains, monkeypatch):
    """The data-vmap layout with the fused path forced: the same chain as
    the XLA scan, step for step."""
    kw = dict(HYBRID, chains=chains)
    if chains == "vmap":
        kw["n_chains"] = 2
    spec = SamplerSpec(**kw)
    hyp = IBPHypers(resample_sigmas=False, resample_alpha=False)
    s_scan = build_sampler(spec, hyp, X)
    monkeypatch.setattr(collapsed, "on_tpu", lambda: True)
    s_fused = build_sampler(spec, hyp, X)
    gs, st = s_scan.init(jax.random.key(7))
    births = 0
    for _ in range(6):
        a, b = s_scan.step(gs, st), s_fused.step(gs, st)
        mism = int(jnp.sum(a[1].Z != b[1].Z))
        assert mism <= MISMATCH_BUDGET, mism
        np.testing.assert_array_equal(np.asarray(a[0].tail_refresh),
                                      np.asarray(b[0].tail_refresh))
        np.testing.assert_array_equal(np.asarray(a[0].tail_sat),
                                      np.asarray(b[0].tail_sat))
        births += int(np.sum(np.asarray(jnp.sum(a[0].active, -1))
                             > np.asarray(jnp.sum(gs.active, -1))))
        gs, st = a
    assert births > 0
    assert np.all(np.asarray(gs.tail_refresh) > 0)


def test_hybrid_step_with_fused_tail_on_shard_map():
    """The shard_map layout (the tail behind the real branch on p′'s
    device) with the fused path forced, on four host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.data import cambridge_data
        from repro.core.ibp import IBPHypers, SamplerSpec, build_sampler
        from repro.core.ibp import collapsed
        X, _, _ = cambridge_data(N=96, seed=4)
        spec = SamplerSpec(P=4, K_max=12, K_tail=4, K_init=2, L=2,
                           data='shardmap', collapsed_backend='fast',
                           chol_refresh=8)
        hyp = IBPHypers()
        s_scan = build_sampler(spec, hyp, X)
        collapsed.on_tpu = lambda: True
        s_fused = build_sampler(spec, hyp, X)
        gs, st = s_scan.init(jax.random.key(2))
        for _ in range(4):
            a, b = s_scan.step(gs, st), s_fused.step(gs, st)
            za = np.asarray(s_scan.to_canonical(a[1]).Z)
            zb = np.asarray(s_fused.to_canonical(b[1]).Z)
            assert int(np.sum(za != zb)) <= 2
            assert int(a[0].tail_refresh) == int(b[0].tail_refresh)
            assert int(a[0].tail_sat) == int(b[0].tail_sat)
            gs, st = a
        assert int(gs.tail_refresh) > 0
        jaxpr = str(jax.make_jaxpr(s_fused._fns.step)(s_fused._Xn, gs, *st))
        assert 'ibp_tail_scan' in jaxpr
        print('OK fused shard_map')
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK fused shard_map" in out.stdout


def test_probe_cadence_is_shared():
    from repro.kernels.tail_scan import kernel

    assert kernel.PROBE_EVERY == PROBE_EVERY
    assert kernel.J_MAX == collapsed.J_MAX
