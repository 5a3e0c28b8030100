"""MCMCDriver backend/knob coverage: the K_max-overflow checkpoint-and-grow
restart, the bounded-staleness knob, multichain checkpoint/resume
(bitwise), and diagnostics in eval records."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore, save_pytree
from repro.core.ibp import IBPHypers
from repro.data import cambridge_data
from repro.runtime import DriverConfig, MCMCDriver


@pytest.fixture(scope="module")
def data():
    X, _, _ = cambridge_data(N=48, sigma_n=0.4, seed=3)
    return X


def test_kmax_overflow_checkpoints_then_grows(tmp_path):
    """Feature-slot overflow checkpoints + raises; restarting with a larger
    K_max pads the checkpointed feature axis and resumes (never silent
    truncation) — DESIGN.md §10."""
    # six strong planted features against K_max=2, with sigma_x held at
    # the true noise so extra variance cannot absorb them: the tail
    # births features the instantiated block has no slot for within the
    # first few iterations, on any PRNG stream
    rng = np.random.default_rng(0)
    Zt = (rng.random((48, 6)) < 0.5).astype(np.float32)
    At = 4.0 * rng.standard_normal((6, 36)).astype(np.float32)
    data = Zt @ At + 0.3 * rng.standard_normal((48, 36)).astype(np.float32)
    hyp = IBPHypers(resample_sigmas=False)
    cfg = DriverConfig(P=3, K_max=2, K_tail=2, K_init=1, L=3, n_iters=40,
                      sigma_x=0.3, sigma_a=4.0,
                      ckpt_every=1000, eval_every=1000,
                      ckpt_dir=str(tmp_path))
    with pytest.raises(RuntimeError, match="overflow"):
        MCMCDriver(data, cfg, hyp).run()
    step = latest_step(str(tmp_path))
    assert step is not None  # overflow wrote a checkpoint first

    # grow-and-restart until the run completes (capacity doubles each time)
    K = cfg.K_max
    for _ in range(4):
        K *= 2
        try:
            gs, ss = MCMCDriver(
                data, dataclasses.replace(cfg, K_max=K), hyp
            ).run()
            break
        except RuntimeError:
            continue
    else:
        pytest.fail("growth never reached sufficient capacity")
    assert int(gs.it) == 40
    assert ss.Z.shape[-1] == K            # feature axis actually grew
    assert int(jnp.max(gs.overflow)) == 0
    assert int(gs.active.sum()) >= 1


def test_kmax_shrink_restart_compacts_features(data, tmp_path):
    """Restoring a checkpoint under a SMALLER K_max compacts the live
    features (plus lowest free slots — the packed-carry block rule) into
    the new capacity and resumes; an impossible shrink refuses loudly
    (DESIGN.md §14)."""
    cfg = DriverConfig(P=3, K_max=16, K_tail=4, K_init=3, L=2, n_iters=6,
                       ckpt_every=3, eval_every=1000,
                       ckpt_dir=str(tmp_path))
    gs, ss = MCMCDriver(data, cfg, IBPHypers()).run()
    n_live = int(gs.active.sum())
    assert 1 <= n_live, "need live features to exercise the shrink"
    K_small = max(6, n_live)
    if K_small >= cfg.K_max:
        pytest.skip(f"chain kept {n_live} live features; nothing to shrink")
    gs2, ss2 = MCMCDriver(
        data, dataclasses.replace(cfg, K_max=K_small, n_iters=10),
        IBPHypers(),
    ).run()
    assert ss2.Z.shape[-1] == K_small      # feature axis actually shrank
    assert int(gs2.it) == 10               # and the run resumed + finished
    assert int(gs2.active.sum()) >= 1
    # refusing case: capacity below the live set must fail loudly, never
    # silently truncate (restores the latest — post-shrink-run — ckpt)
    n_live2 = int(gs2.active.sum())
    if n_live2 >= 2:
        with pytest.raises(ValueError, match="shrink"):
            MCMCDriver(
                data,
                dataclasses.replace(
                    cfg, K_max=n_live2 - 1, K_init=1, K_tail=2),
                IBPHypers(),
            ).run()


def test_stale_sync_knob_runs_and_differs(data, tmp_path):
    """stale_sync > 0 interleaves sync-free sub-iteration passes: the run
    stays finite/sane but takes a different (non-exact) trajectory."""
    mk = lambda sub, s: DriverConfig(
        P=3, K_max=12, K_tail=6, L=2, n_iters=8, ckpt_every=1000,
        eval_every=1000, stale_sync=s, ckpt_dir=str(tmp_path / sub))
    gs0, _ = MCMCDriver(data, mk("a", 0), IBPHypers()).run()
    gs2, _ = MCMCDriver(data, mk("b", 2), IBPHypers()).run()
    assert np.isfinite(float(gs2.sigma_x))
    assert 1 <= int(gs2.active.sum()) <= 12
    # the stale trajectory consumed different randomness -> different state
    assert float(gs0.sigma_x) != float(gs2.sigma_x)


def test_stale_pass_key_advance_distinct_from_consumed_stream(data):
    """Regression pin: the key a stale pass hands forward (fold 14) must
    differ from the key its sweeps consumed (fold 13) — otherwise the next
    iteration's sub-iterations replay the same per-(shard, l) uniforms."""
    from repro.core.ibp import SamplerSpec, build_sampler

    s = build_sampler(SamplerSpec(P=3, K_max=12, K_tail=6, K_init=3, L=2),
                      IBPHypers(), data)
    gs, st = s.init(jax.random.key(0))
    gs2, _ = s.stale(gs, st)
    kd = lambda k: np.asarray(jax.random.key_data(k))
    assert not np.array_equal(kd(gs2.key),
                              kd(jax.random.fold_in(gs.key, 13)))
    np.testing.assert_array_equal(kd(gs2.key),
                                  kd(jax.random.fold_in(gs.key, 14)))


def test_stale_pass_shardmap_matches_vmap(data):
    """The collective-free shard_map stale pass is bitwise-equivalent to
    the vmap stale pass (P=1 mesh runs in-process on one device)."""
    from repro.core.ibp import SamplerSpec, build_sampler

    spec = SamplerSpec(P=1, K_max=12, K_tail=6, K_init=3, L=2)
    sv = build_sampler(spec, IBPHypers(), data)
    sm = build_sampler(spec.replace(data="shardmap"), IBPHypers(), data)
    gs, st_v = sv.init(jax.random.key(4))
    st_m = sm.from_canonical(sv.to_canonical(st_v))  # identical start
    gs_v, ss_v = sv.stale(gs, st_v)
    gs_s, ss_s = sm.stale(gs, st_m)
    np.testing.assert_array_equal(np.asarray(sv.to_canonical(ss_v).Z),
                                  np.asarray(sm.to_canonical(ss_s).Z))
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(gs_v.key)),
        np.asarray(jax.random.key_data(gs_s.key)))


def test_multichain_resumes_bitwise_from_checkpoint(data, tmp_path):
    """Straight-through multichain run == crash/resume run, bitwise, for
    every chain (the checkpoint carries the per-chain keys)."""
    mk = lambda sub, n: DriverConfig(
        P=3, K_max=12, K_tail=6, L=3, n_iters=n, ckpt_every=5,
        eval_every=100, driver="multichain", n_chains=3,
        ckpt_dir=str(tmp_path / sub))
    gs_a, ss_a = MCMCDriver(data, mk("full", 10), IBPHypers()).run()
    MCMCDriver(data, mk("half", 5), IBPHypers()).run()
    gs_b, ss_b = MCMCDriver(data, mk("half", 10), IBPHypers()).run()
    np.testing.assert_array_equal(np.asarray(ss_a.Z), np.asarray(ss_b.Z))
    np.testing.assert_array_equal(np.asarray(gs_a.sigma_x),
                                  np.asarray(gs_b.sigma_x))
    np.testing.assert_array_equal(np.asarray(gs_a.A), np.asarray(gs_b.A))
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(gs_a.key)),
        np.asarray(jax.random.key_data(gs_b.key)))


def test_multichain_eval_records_diagnostics(data, tmp_path):
    """C >= 4 vectorized chains advance in one jitted step and eval
    records carry split-R-hat / ESS / MCSE plus per-chain stats."""
    cfg = DriverConfig(P=3, K_max=12, K_tail=6, L=3, n_iters=16,
                      ckpt_every=1000, eval_every=8, driver="multichain",
                      n_chains=4, ckpt_dir=str(tmp_path))
    drv = MCMCDriver(data, cfg, IBPHypers())
    gs, ss = drv.run()
    assert ss.Z.shape[0] == 4             # chain axis
    rec = drv.history[-1]
    for k in ("sigma_x_rhat", "sigma_x_ess", "sigma_x_mcse", "K_rhat"):
        assert k in rec, rec.keys()
    assert len(rec["K_chains"]) == 4
    assert len(rec["sigma_x_chains"]) == 4
    # chains are genuinely independent: distinct trajectories
    assert len({round(s, 6) for s in rec["sigma_x_chains"]}) > 1
    # trace has one (C,) row per iteration
    assert len(drv.trace["sigma_x"]) == 16
    assert drv.trace["sigma_x"][0].shape == (4,)


def test_checkpoint_interchange_vmap_to_multichain_rejected(data, tmp_path):
    """A single-chain checkpoint cannot silently restore under a
    chain-batched template — leaf shapes disagree loudly."""
    cfg = DriverConfig(P=3, K_max=12, K_tail=6, L=2, n_iters=4,
                      ckpt_every=2, eval_every=100, ckpt_dir=str(tmp_path))
    MCMCDriver(data, cfg, IBPHypers()).run()
    cfg_mc = dataclasses.replace(cfg, driver="multichain", n_chains=2,
                                 n_iters=6)
    with pytest.raises(ValueError, match="chain"):
        MCMCDriver(data, cfg_mc, IBPHypers()).run()


def test_multichain_resume_rejects_changed_chain_count(data, tmp_path):
    """n_chains is part of the checkpointed state: resuming with a
    different chain count fails loudly instead of silently keeping the
    old C while diagnostics claim the new one."""
    mk = lambda c, n: DriverConfig(
        P=3, K_max=12, K_tail=6, L=2, n_iters=n, ckpt_every=2,
        eval_every=100, driver="multichain", n_chains=c,
        ckpt_dir=str(tmp_path))
    MCMCDriver(data, mk(3, 4), IBPHypers()).run()
    with pytest.raises(ValueError, match="n_chains"):
        MCMCDriver(data, mk(8, 8), IBPHypers()).run()


def test_adaptive_k_tail_grows_on_saturation(tmp_path):
    """k_tail_grow > 0: tail saturation (capacity-vetoed accepted MH
    births, gs.tail_sat) at a checkpoint boundary doubles K_tail
    in-process — the run continues with wider tail buffers, the ceiling
    is K_max, and eval records surface K_tail + tail_sat."""
    rng = np.random.default_rng(0)
    Zt = (rng.random((60, 10)) < 0.4).astype(np.float32)
    At = rng.standard_normal((10, 16)).astype(np.float32) * 1.5
    X = Zt @ At + 0.3 * rng.standard_normal((60, 16)).astype(np.float32)
    cfg = DriverConfig(P=3, K_max=16, K_tail=1, K_init=1, L=3, n_iters=30,
                       ckpt_every=5, eval_every=10, k_tail_grow=3,
                       alpha=8.0, ckpt_dir=str(tmp_path))
    drv = MCMCDriver(X, cfg, IBPHypers())
    gs, ss = drv.run()
    assert int(gs.it) == 30                       # ran to completion
    assert drv.spec.K_tail > 1                    # growth actually fired
    assert drv.spec.K_tail <= cfg.K_max
    assert ss.Z_tail.shape[-1] == drv.spec.K_tail  # buffers follow the spec
    rec = drv.history[-1]
    assert rec["K_tail"] == drv.spec.K_tail
    assert rec["tail_sat"] >= 0
    assert drv._tail_growths <= cfg.k_tail_grow


def test_k_tail_fixed_when_grow_disabled(data, tmp_path):
    """k_tail_grow=0 (default): saturation may accrue but K_tail never
    moves — the historical fixed-truncation behavior."""
    cfg = DriverConfig(P=3, K_max=12, K_tail=2, K_init=2, L=3, n_iters=12,
                       ckpt_every=4, eval_every=6, alpha=6.0,
                       ckpt_dir=str(tmp_path))
    drv = MCMCDriver(data, cfg, IBPHypers())
    gs, ss = drv.run()
    assert drv.spec.K_tail == 2
    assert ss.Z_tail.shape[-1] == 2
    assert drv.history[-1]["K_tail"] == 2
    assert "tail_sat" in drv.history[-1]


def _refresh_cfg(ckpt_dir, n_iters):
    # N_p = 16 rows per shard, a refresh every R = 4 rows
    return DriverConfig(P=3, K_max=12, K_tail=4, K_init=2, L=2,
                        n_iters=n_iters, ckpt_every=2, eval_every=1,
                        collapsed_backend="fast", chol_refresh=4,
                        ckpt_dir=str(ckpt_dir))


def test_tail_refresh_counts_the_refresh_cadence(data, tmp_path):
    """gs.tail_refresh counts the tail rows that took the exact
    refactorization: at least L x floor(N_p / R) an iteration from the
    cadence alone, and every eval record carries the running count."""
    cfg = _refresh_cfg(tmp_path, 4)
    drv = MCMCDriver(data, cfg, IBPHypers())
    gs, _ = drv.run()
    per_iter = cfg.L * ((data.shape[0] // cfg.P) // cfg.chol_refresh)
    assert int(gs.tail_refresh) >= cfg.n_iters * per_iter
    counts = [rec["tail_refresh"] for rec in drv.history]
    assert counts[-1] == int(gs.tail_refresh)
    assert all(b - a >= per_iter for a, b in zip([0] + counts, counts))


def test_checkpoint_without_tail_refresh_restores_it_at_zero(data,
                                                              tmp_path):
    """A checkpoint written before gs carried tail_refresh resumes with
    the count at 0; the rest of the state resumes as it was."""
    MCMCDriver(data, _refresh_cfg(tmp_path / "new", 2), IBPHypers()).run()
    drv = MCMCDriver(data, _refresh_cfg(tmp_path / "new", 3), IBPHypers())
    tpl = drv._template()
    blob, step = restore(str(tmp_path / "new"), tpl)
    assert int(blob["gs"].tail_refresh) > 0
    old = dict(blob, gs=dataclasses.replace(blob["gs"], tail_refresh=None))
    save_pytree(str(tmp_path / "old"), old, step)

    gs_new, ss_new = drv.run()
    gs_old, ss_old = MCMCDriver(data, _refresh_cfg(tmp_path / "old", 3),
                                IBPHypers()).run()
    np.testing.assert_array_equal(np.asarray(ss_old.Z),
                                  np.asarray(ss_new.Z))
    assert int(gs_old.it) == int(gs_new.it) == 3
    # the old checkpoint's count restarts at 0: only iteration 3's remain
    assert int(gs_old.tail_refresh) == (int(gs_new.tail_refresh)
                                        - int(blob["gs"].tail_refresh))


@pytest.mark.parametrize("fused", [False, True])
def test_eval_record_names_the_tail_path(data, tmp_path, monkeypatch,
                                         fused):
    """Every eval record says which path the tail's row scan took: the
    fused kernel (on a TPU; forced here, in interpret mode) or the scan.
    The fused run counts the same refreshes as the scan."""
    from repro.core.ibp import collapsed

    if fused:
        monkeypatch.setattr(collapsed, "on_tpu", lambda: True)
    drv = MCMCDriver(data, _refresh_cfg(tmp_path, 2), IBPHypers())
    gs, _ = drv.run()
    assert [r["tail_path"] for r in drv.history] == (
        ["fused" if fused else "scan"] * 2)
    cfg = drv.cfg
    per_iter = cfg.L * ((data.shape[0] // cfg.P) // cfg.chol_refresh)
    assert int(gs.tail_refresh) >= cfg.n_iters * per_iter
