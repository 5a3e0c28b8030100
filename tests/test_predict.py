"""Posterior-predictive serving subsystem (core/ibp/predict, DESIGN.md §15):

* encode's Rao-Blackwellized Gibbs marginals vs the exact 2^K
  enumeration oracle at small K;
* impute equals the exact conditional mean in the sigma -> 0 limit;
* bank save/restore roundtrip, including mixed live-K buckets across
  samples and bucket-ladder packing;
* the batched per-row joint log-likelihood (and the logsumexp mixture)
  vs the naive float64 numpy oracle to 1e-6;
* driver harvest integration (chain-aware, restorable with no sampler
  state) and the harvest spec knobs' validation;
* the mesh-sharded scorer vs the unsharded op;
* serve_ibp's pad-to-bucket microbatching helpers.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.ibp import IBPHypers, SamplerSpec
from repro.core.ibp import predict
from repro.core.ibp.predict import BankBuilder, SampleBank


def make_bank(S=3, K_max=16, K_live=5, D=8, sigma_x=0.6, seed=0,
              k_lives=None):
    rng = np.random.default_rng(seed)
    bb = BankBuilder(K_max)
    lives = k_lives if k_lives is not None else [K_live] * S
    for s, kl in enumerate(lives):
        act = np.zeros(K_max, np.float32)
        act[:kl] = 1.0
        bb.add(rng.normal(size=(K_max, D)).astype(np.float32) * act[:, None],
               rng.uniform(0.2, 0.8, K_max).astype(np.float32) * act,
               act, sigma_x, 1.0, 2.0, chain=s % 2, it=10 + s)
    return bb.build()


# --------------------------------------------------------------------------
# encode vs exact enumeration
# --------------------------------------------------------------------------


def test_encode_matches_enumeration_small_k():
    """RB'd Gibbs marginals converge to the exact 2^K posterior."""
    bank = make_bank(S=2, K_max=8, K_live=4, D=6, sigma_x=0.8, seed=1)
    rng = np.random.default_rng(2)
    X = rng.normal(size=(5, 6)).astype(np.float32)
    probs = predict.encode(bank, X, jax.random.key(0), n_sweeps=192)
    for s in range(bank.S):
        marg, _, _ = predict.exact_posterior(
            bank.A[s], bank.pi[s], bank.active[s], bank.sigma_x[s], X)
        err = np.max(np.abs(np.asarray(probs[s]) - np.asarray(marg)))
        assert err < 0.12, f"sample {s}: RB marginals off by {err}"


def test_encode_masked_matches_masked_enumeration():
    """Masked-Gaussian conditioning: only observed dims enter."""
    bank = make_bank(S=1, K_max=8, K_live=3, D=6, sigma_x=0.8, seed=3)
    rng = np.random.default_rng(4)
    X = rng.normal(size=(4, 6)).astype(np.float32)
    mask = (rng.random((4, 6)) > 0.4).astype(np.float32)
    mask[:, 0] = 1.0
    probs = predict.encode(bank, X, jax.random.key(1), mask=mask,
                           n_sweeps=192)
    marg, _, _ = predict.exact_posterior(
        bank.A[0], bank.pi[0], bank.active[0], bank.sigma_x[0], X,
        mask=mask)
    err = np.max(np.abs(np.asarray(probs[0]) - np.asarray(marg)))
    assert err < 0.12, f"masked RB marginals off by {err}"


def test_exact_posterior_rejects_large_k():
    A = np.zeros((predict.ENUM_MAX_K + 1, 4), np.float32)
    with pytest.raises(ValueError, match="enumeration"):
        predict.exact_posterior(A, np.zeros(A.shape[0]),
                                np.zeros(A.shape[0]), 1.0,
                                np.zeros((2, 4), np.float32))


# --------------------------------------------------------------------------
# impute: sigma -> 0 limit
# --------------------------------------------------------------------------


def test_impute_sigma_zero_limit_equals_exact_conditional_mean():
    """As sigma_x -> 0 the posterior concentrates and E[x_miss | x_obs]
    is the exact conditional mean — which the enumeration oracle
    computes and the Gibbs imputation must match."""
    rng = np.random.default_rng(5)
    K_max, D = 8, 10
    A = np.zeros((K_max, D), np.float32)
    A[:3] = rng.normal(size=(3, D)).astype(np.float32)
    act = np.zeros(K_max, np.float32)
    act[:3] = 1.0
    bb = BankBuilder(K_max)
    sigma = 0.02
    bb.add(A, 0.5 * act, act, sigma, 1.0, 2.0)
    bank = bb.build()
    z_true = np.array([1.0, 0.0, 1.0])
    x_full = z_true @ A[:3]
    mask = np.ones((1, D), np.float32)
    mask[0, 6:] = 0.0  # last 4 dims missing
    X = (x_full * mask[0]).reshape(1, D).astype(np.float32)
    out = predict.impute(bank, X, mask, jax.random.key(2), n_sweeps=24)
    _, _, cond_mean = predict.exact_posterior(
        bank.A[0], bank.pi[0], bank.active[0], bank.sigma_x[0], X,
        mask=mask)
    miss = mask[0] < 0.5
    np.testing.assert_allclose(np.asarray(out)[0, miss],
                               np.asarray(cond_mean)[0, miss], atol=1e-2)
    np.testing.assert_allclose(np.asarray(out)[0, miss], x_full[miss],
                               atol=1e-2)
    # observed entries pass through untouched
    np.testing.assert_array_equal(np.asarray(out)[0, ~miss],
                                  X[0, ~miss])


# --------------------------------------------------------------------------
# bank packing + persistence
# --------------------------------------------------------------------------


def test_bank_packs_to_bucket_ladder():
    bank = make_bank(S=3, K_max=64, K_live=5, D=4)
    assert bank.K == 8  # smallest ladder bucket holding 5 live features


def test_bank_roundtrip_mixed_live_buckets(tmp_path):
    """Samples from different occupancy regimes pack to ONE bank bucket
    and survive save/load bitwise."""
    bank = make_bank(S=4, K_max=32, D=6, k_lives=[2, 9, 4, 7], seed=7)
    assert bank.K == 16  # bucket for the widest live set (9)
    path = str(tmp_path / "bank.npz")
    bank.save(path)
    back = SampleBank.load(path)
    import dataclasses
    for f in dataclasses.fields(SampleBank):
        np.testing.assert_array_equal(
            np.asarray(getattr(bank, f.name)),
            np.asarray(getattr(back, f.name)), err_msg=f.name)
    # and the restored bank scores identically
    X = np.random.default_rng(8).normal(size=(3, 6)).astype(np.float32)
    key = jax.random.key(3)
    np.testing.assert_array_equal(
        np.asarray(predict.predictive_loglik(bank, X, key)),
        np.asarray(predict.predictive_loglik(back, X, key)))


def test_bank_load_rejects_wrong_format(tmp_path):
    from repro.checkpoint import save_arrays
    path = str(tmp_path / "bad.npz")
    save_arrays(path, {"_format": np.asarray(99), "A": np.zeros((1, 2, 2))})
    with pytest.raises(ValueError, match="format"):
        SampleBank.load(path)


def test_empty_builder_build_raises():
    with pytest.raises(ValueError, match="empty bank"):
        BankBuilder(8).build()


# --------------------------------------------------------------------------
# predictive_loglik vs the numpy oracle (1e-6)
# --------------------------------------------------------------------------


def test_rows_joint_loglik_matches_numpy_oracle_1e6():
    """The jitted batched scorer's per-row joint ll (and its logsumexp
    mixture) match the explicit float64 numpy loop to 1e-6."""
    with jax.enable_x64(True):
        rng = np.random.default_rng(9)
        S, K, D, B = 3, 6, 7, 4
        bank = make_bank(S=S, K_max=8, K_live=5, D=D, seed=9)
        bank = jax.tree.map(
            lambda x: jnp.asarray(np.asarray(x), jnp.float64)
            if np.asarray(x).dtype.kind == "f" else jnp.asarray(x), bank)
        X = jnp.asarray(rng.normal(size=(B, D)))
        mask = jnp.asarray((rng.random((B, D)) > 0.3).astype(np.float64))
        _, Z, lls = predict._score_bank(
            bank, X, mask, jax.random.key(4), 3, 1, masked=True)
        oracle = np.stack([
            predict.joint_loglik_np(X, Z[s], bank.A[s], bank.pi[s],
                                    bank.active[s], bank.sigma_x[s],
                                    mask=mask)
            for s in range(S)
        ])
        np.testing.assert_allclose(np.asarray(lls), oracle,
                                   rtol=1e-6, atol=1e-6)
        mix = jax.scipy.special.logsumexp(jnp.asarray(oracle), axis=0) \
            - np.log(S)
        got, per = predict.predictive_loglik(
            bank, X, jax.random.key(4), mask=mask, per_sample=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(mix),
                                   rtol=1e-6, atol=1e-6)


def test_heldout_joint_loglik_is_canonical_reexport():
    """diagnostics re-exports predict's implementation (dedup)."""
    from repro.core.ibp import diagnostics
    assert diagnostics.heldout_joint_loglik is predict.heldout_joint_loglik
    assert diagnostics.train_joint_loglik is predict.train_joint_loglik


def test_anomaly_is_negative_mixture():
    bank = make_bank()
    X = np.random.default_rng(11).normal(size=(3, 8)).astype(np.float32)
    key = jax.random.key(5)
    np.testing.assert_array_equal(
        np.asarray(predict.anomaly_score(bank, X, key)),
        -np.asarray(predict.predictive_loglik(bank, X, key)))


def test_naive_loop_finite_and_shaped():
    bank = make_bank()
    X = np.random.default_rng(12).normal(size=(5, 8)).astype(np.float32)
    out = predict.predictive_loglik_naive(bank, X, jax.random.key(6))
    assert out.shape == (5,)
    assert np.all(np.isfinite(np.asarray(out)))


# --------------------------------------------------------------------------
# harvest wiring: spec validation + driver integration
# --------------------------------------------------------------------------


def test_spec_validates_harvest_knobs():
    with pytest.raises(ValueError, match="harvest_every"):
        SamplerSpec(harvest_every=-1)
    with pytest.raises(ValueError, match="harvest_burn"):
        SamplerSpec(harvest_burn=1.0)
    with pytest.raises(ValueError, match="harvest_burn"):
        SamplerSpec(harvest_burn=-0.1)
    SamplerSpec(harvest_every=5, harvest_burn=0.0)  # valid


def test_driver_harvests_chain_aware_bank(tmp_path):
    """A multichain run harvests one sample per chain past burn-in, the
    bank rides the checkpoint cadence, and the persisted npz restores
    with NO sampler state."""
    from repro.runtime import MCMCDriver

    rng = np.random.default_rng(13)
    X = rng.normal(size=(24, 5)).astype(np.float32)
    spec = SamplerSpec(
        P=2, K_max=8, K_tail=4, K_init=2, L=2, n_iters=8, eval_every=4,
        ckpt_every=4, ckpt_dir=str(tmp_path / "ck"),
        chains="vmap", data="vmap", n_chains=2,
        harvest_every=2, harvest_burn=0.25,
        bank_path=str(tmp_path / "bank.npz"),
    )
    drv = MCMCDriver(X, spec, IBPHypers())
    drv.run()
    # burn = int(0.25 * 8) = 2 -> harvests at iterations 4, 6, 8 x 2 chains
    assert len(drv.bank_builder) == 6
    bank = SampleBank.load(str(tmp_path / "bank.npz"))
    assert bank.S == 6
    assert sorted(set(np.asarray(bank.chain).tolist())) == [0, 1]
    assert sorted(set(np.asarray(bank.it).tolist())) == [4, 6, 8]
    # the bank is a bucket of K_max=8 at most
    assert bank.K <= 8
    # and it scores data without any sampler machinery
    ll = predict.predictive_loglik(bank, X[:4], jax.random.key(0))
    assert np.all(np.isfinite(np.asarray(ll)))


def test_driver_restart_extends_bank(tmp_path):
    """A restart re-seeds the builder from the persisted bank instead of
    overwriting it with a shorter ensemble."""
    from repro.runtime import MCMCDriver

    rng = np.random.default_rng(14)
    X = rng.normal(size=(16, 4)).astype(np.float32)
    kw = dict(P=2, K_max=8, K_tail=4, K_init=2, L=2, eval_every=4,
              ckpt_every=2, ckpt_dir=str(tmp_path / "ck"),
              harvest_every=1, harvest_burn=0.0,
              bank_path=str(tmp_path / "bank.npz"))
    drv = MCMCDriver(X, SamplerSpec(n_iters=4, **kw), IBPHypers())
    with pytest.raises(RuntimeError, match="injected crash"):
        drv.run(crash_at=3)  # harvested its 1, 2; ckpt at 2
    drv2 = MCMCDriver(X, SamplerSpec(n_iters=4, **kw), IBPHypers())
    drv2.run()
    bank = SampleBank.load(str(tmp_path / "bank.npz"))
    # resumed from the step-2 checkpoint with its 2 persisted samples,
    # then harvested 3 and 4
    assert bank.S == 4
    assert sorted(np.asarray(bank.it).tolist()) == [1, 2, 3, 4]


def test_same_driver_rerun_does_not_duplicate_harvests(tmp_path):
    """Retrying run() on the SAME driver object after a crash rewinds to
    the checkpoint and re-harvests the rewound iterations — the builder
    must reconcile (prune past the restored step) so every draw appears
    exactly once."""
    from repro.runtime import MCMCDriver

    rng = np.random.default_rng(21)
    X = rng.normal(size=(16, 4)).astype(np.float32)
    spec = SamplerSpec(
        P=2, K_max=8, K_tail=4, K_init=2, L=2, n_iters=4, eval_every=4,
        ckpt_every=2, ckpt_dir=str(tmp_path / "ck"),
        harvest_every=1, harvest_burn=0.0,
        bank_path=str(tmp_path / "bank.npz"))
    drv = MCMCDriver(X, spec, IBPHypers())
    with pytest.raises(RuntimeError, match="injected crash"):
        drv.run(crash_at=3)  # harvested 1..3 in memory; ckpt at 2
    drv.run()  # same object: rewinds to 2, re-runs 3 and 4
    its = sorted(np.asarray(SampleBank.load(spec.bank_path).it).tolist())
    assert its == [1, 2, 3, 4], its


# --------------------------------------------------------------------------
# mesh-sharded scoring
# --------------------------------------------------------------------------


def test_sharded_scorer_matches_unsharded():
    bank = make_bank(S=2, K_max=8, K_live=3, D=6, seed=15)
    X = np.random.default_rng(16).normal(size=(6, 6)).astype(np.float32)
    mesh = jax.make_mesh((1,), ("data",))
    score = predict.make_sharded_scorer(bank, mesh, n_sweeps=3)
    key = jax.random.key(7)
    got = np.asarray(score(jnp.asarray(X), key))
    # one shard folds in axis index 0
    want = np.asarray(predict.predictive_loglik(
        bank, X, jax.random.fold_in(key, 0), n_sweeps=3))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# serve_ibp microbatching helpers
# --------------------------------------------------------------------------


def test_serve_row_buckets_and_padding():
    from repro.launch.serve_ibp import pad_to_bucket, row_buckets

    assert row_buckets(256) == (8, 16, 32, 64, 128, 256)
    assert row_buckets(8) == (8,)
    assert row_buckets(48) == (8, 16, 32, 48)
    bs = row_buckets(64)
    X = np.ones((5, 3), np.float32)
    P = pad_to_bucket(X, bs)
    assert P.shape == (8, 3)
    np.testing.assert_array_equal(P[:5], X)
    assert not P[5:].any()
    assert pad_to_bucket(np.ones((16, 3), np.float32), bs).shape == (16, 3)
