"""Composable sampler API (DESIGN.md §13): spec validation rejects every
invalid knob combination loudly; the historical drivers are degenerate
points of the chains x data grid; the composed mesh layout matches its
degenerate neighbours bitwise; and checkpoints interchange across all
four drivers (chain count preserved).

Multi-device cases run in subprocesses with forced host devices (same
pattern as tests/test_distributed.py — the main pytest process keeps a
single CPU device)."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.ibp import IBPHypers, SamplerSpec, build_sampler
from repro.core.ibp.api import DRIVERS
from repro.data import cambridge_data
from repro.runtime import DriverConfig, MCMCDriver, as_spec

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(code: str, n_devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


# ---------------------------------------------------------------------------
# spec validation: every invalid combination fails loudly at construction
# ---------------------------------------------------------------------------

INVALID_SPECS = [
    # (kwargs, message fragment)
    (dict(chains="tree"), "chains"),
    (dict(data="pmap"), "data"),
    (dict(chains="vmap", data="shardmap"), "vmap"),
    (dict(n_chains=0, chains="vmap"), "n_chains"),
    (dict(n_chains=-1, chains="mesh"), "n_chains"),
    (dict(n_chains=2), "chain axis"),          # chains="none" default
    (dict(sync="lazy"), "sync"),
    (dict(sync="fused"), "fused"),             # fused needs data="shardmap"
    (dict(sync="fused", chains="mesh", data="vmap"), "fused"),
    (dict(backend="cuda"), "backend"),
    (dict(collapsed_backend="magic"), "collapsed_backend"),
    (dict(collapsed_backend="pallas"), "collapsed_backend"),
    (dict(chol_refresh=0), "chol_refresh"),
    (dict(k_live_buckets="auto"), "k_live_buckets"),
    (dict(k_live_buckets=""), "k_live_buckets"),
    (dict(k_live_buckets="off"), "k_live_buckets"),
    (dict(P=0), "P="),
    (dict(L=0), "L="),
    (dict(K_max=0), "K_max"),
    (dict(K_tail=0), "K_tail"),
    (dict(K_tail=64), "K_tail"),               # > K_max default 32: tail
    #                                            promotion needs free slots
    (dict(K_max=4, K_tail=8), "exceeds"),
    (dict(k_tail_grow=-1), "k_tail_grow"),
    (dict(K_init=33), "K_init"),               # > K_max default 32
    (dict(K_init=-1), "K_init"),
    (dict(stale_sync=-1), "stale_sync"),       # used to skip silently
    (dict(overflow_every=0), "overflow_every"),  # used to ZeroDivisionError
    (dict(n_iters=0), "n_iters"),
    (dict(eval_every=0), "eval_every"),
    (dict(ckpt_every=0), "ckpt_every"),
]


@pytest.mark.parametrize("kw,frag", INVALID_SPECS,
                         ids=[f"{list(kw)[0]}={list(kw.values())[0]}"
                              for kw, _ in INVALID_SPECS])
def test_spec_rejects_invalid_combinations(kw, frag):
    with pytest.raises(ValueError, match=frag):
        SamplerSpec(**kw)


def test_spec_valid_layout_grid():
    """Every supported chains x data combination constructs, and the
    historical driver names map onto the right grid points."""
    assert SamplerSpec().driver == "vmap"
    assert SamplerSpec(chains="vmap", n_chains=4).driver == "multichain"
    assert SamplerSpec(data="shardmap").driver == "shardmap"
    m = SamplerSpec(chains="mesh", data="shardmap", n_chains=2)
    assert m.driver == "mesh" and m.devices_needed == 2 * m.P
    # chains-mesh with simulated data shards is also a valid layout
    mv = SamplerSpec(chains="mesh", data="vmap", n_chains=2)
    assert mv.driver == "mesh" and mv.devices_needed == 2
    for name in DRIVERS:
        spec = SamplerSpec.for_driver(name, n_chains=2 if
                                      DRIVERS[name][0] != "none" else 1)
        assert spec.driver == name
    with pytest.raises(ValueError, match="driver"):
        SamplerSpec.for_driver("pmap")


def test_driverconfig_shim_maps_onto_spec():
    """The deprecated scattered-kwarg surface maps 1:1 onto the spec —
    and invalid old-style combinations still fail loudly (through spec
    validation now)."""
    cfg = DriverConfig(P=3, K_max=12, driver="multichain", n_chains=4,
                       stale_sync=2, collapsed_backend="ref",
                       ckpt_dir="/tmp/x")
    spec = as_spec(cfg)
    assert (spec.chains, spec.data) == ("vmap", "vmap")
    assert spec.n_chains == 4 and spec.P == 3 and spec.K_max == 12
    assert spec.stale_sync == 2 and spec.ckpt_dir == "/tmp/x"
    assert spec.collapsed_backend == "ref"
    # passing a spec through as_spec is the identity
    assert as_spec(spec) is spec
    with pytest.raises(ValueError):
        as_spec(DriverConfig(driver="nope"))
    with pytest.raises(ValueError):   # n_chains > 1 needs a chainful driver
        as_spec(DriverConfig(driver="vmap", n_chains=2))
    with pytest.raises(ValueError):   # fused sync needs a collective layout
        as_spec(DriverConfig(driver="vmap", sync="fused"))
    # the collapsed tail default is now the certified-equivalent fast path
    assert DriverConfig().collapsed_backend == "fast"
    assert SamplerSpec().collapsed_backend == "fast"
    # the tail always carries G: "on" is the one value, through the shim
    assert DriverConfig().k_live_buckets == "on"
    assert SamplerSpec().k_live_buckets == "on"
    with pytest.raises(ValueError, match="k_live_buckets"):
        as_spec(DriverConfig(k_live_buckets="off"))


def test_build_sampler_rejects_insufficient_devices():
    """Mesh layouts check the device budget loudly at build time (the
    main pytest process has exactly one CPU device)."""
    X, _, _ = cambridge_data(N=24, seed=0)
    spec = SamplerSpec(P=4, chains="mesh", data="shardmap", n_chains=2)
    with pytest.raises(ValueError, match="devices"):
        build_sampler(spec, IBPHypers(), X)


def test_sampler_protocol_canonical_roundtrip():
    """init/step/stale/to_canonical/from_canonical work uniformly; the
    canonical layout round-trips bitwise."""
    X, _, _ = cambridge_data(N=24, seed=1)
    for spec in (SamplerSpec(P=2, K_max=8, K_tail=4, K_init=2, L=2),
                 SamplerSpec(P=2, K_max=8, K_tail=4, K_init=2, L=2,
                             chains="vmap", n_chains=2),
                 SamplerSpec(P=1, K_max=8, K_tail=4, K_init=2, L=2,
                             data="shardmap")):
        s = build_sampler(spec, IBPHypers(), X)
        gs, st = s.init(jax.random.key(0))
        gs, st = s.step(gs, st)
        gs, st = s.stale(gs, st)
        ss = s.to_canonical(st)
        assert ss.Z.shape[-3:] == (spec.P, 24 // spec.P, spec.K_max)
        st2 = s.from_canonical(ss)
        np.testing.assert_array_equal(np.asarray(s.to_canonical(st2).Z),
                                      np.asarray(ss.Z))


# ---------------------------------------------------------------------------
# composed mesh layout: bitwise-degenerate to its neighbours
# ---------------------------------------------------------------------------

def test_mesh_Cx1_matches_multichain_bitwise():
    """mesh with C chains x 1 data shard advances the SAME trajectories as
    the vmapped multichain layout: bitwise Z and PRNG keys, float scalars
    to reduction-order ULPs."""
    out = run_with_devices("""
        import jax, numpy as np
        from repro.core.ibp import IBPHypers, SamplerSpec, build_sampler
        from repro.data import cambridge_data
        X, _, _ = cambridge_data(N=48, sigma_n=0.4, seed=3)
        kw = dict(P=1, K_max=12, K_tail=6, K_init=3, L=2, n_chains=2)
        a = build_sampler(SamplerSpec(chains='mesh', data='shardmap', **kw),
                          IBPHypers(), X)
        b = build_sampler(SamplerSpec(chains='vmap', data='vmap', **kw),
                          IBPHypers(), X)
        ga, sa = a.init(jax.random.key(7))
        gb, sb = b.init(jax.random.key(7))
        for _ in range(5):
            ga, sa = a.step(ga, sa)
            gb, sb = b.step(gb, sb)
        np.testing.assert_array_equal(np.asarray(a.to_canonical(sa).Z),
                                      np.asarray(b.to_canonical(sb).Z))
        np.testing.assert_array_equal(
            np.asarray(jax.random.key_data(ga.key)),
            np.asarray(jax.random.key_data(gb.key)))
        np.testing.assert_allclose(np.asarray(ga.sigma_x),
                                   np.asarray(gb.sigma_x), rtol=1e-5)
        ga, sa = a.stale(ga, sa)
        gb, sb = b.stale(gb, sb)
        np.testing.assert_array_equal(np.asarray(a.to_canonical(sa).Z),
                                      np.asarray(b.to_canonical(sb).Z))
        print('OK mesh Cx1 == multichain')
    """, n_devices=2)
    assert "OK mesh Cx1 == multichain" in out


def test_mesh_1xP_matches_shardmap_bitwise():
    """mesh with 1 chain x P data shards computes the SAME step as the
    chainless shardmap layout from the same canonical state (init differs
    by design: chainful layouts split the key per chain)."""
    out = run_with_devices("""
        import jax, numpy as np
        from repro.core.ibp import (HybridShard, IBPHypers, SamplerSpec,
                                    build_sampler)
        from repro.data import cambridge_data
        X, _, _ = cambridge_data(N=48, sigma_n=0.4, seed=3)
        kw = dict(P=4, K_max=12, K_tail=6, K_init=3, L=2)
        c = build_sampler(SamplerSpec(chains='mesh', data='shardmap',
                                      n_chains=1, **kw), IBPHypers(), X)
        d = build_sampler(SamplerSpec(data='shardmap', **kw),
                          IBPHypers(), X)
        gd, sd = d.init(jax.random.key(9))
        ss_d = d.to_canonical(sd)
        gc = jax.tree.map(lambda x: x[None], gd)       # lift to C=1
        sc = c.from_canonical(HybridShard(
            Z=ss_d.Z[None], Z_tail=ss_d.Z_tail[None],
            tail_active=ss_d.tail_active[None]))
        for _ in range(5):
            gc, sc = c.step(gc, sc)
            gd, sd = d.step(gd, sd)
        np.testing.assert_array_equal(np.asarray(c.to_canonical(sc).Z)[0],
                                      np.asarray(d.to_canonical(sd).Z))
        np.testing.assert_array_equal(
            np.asarray(jax.random.key_data(gc.key))[0],
            np.asarray(jax.random.key_data(gd.key)))
        np.testing.assert_allclose(float(gc.sigma_x[0]), float(gd.sigma_x),
                                   rtol=1e-5)
        gc, sc = c.stale(gc, sc)
        gd, sd = d.stale(gd, sd)
        np.testing.assert_array_equal(np.asarray(c.to_canonical(sc).Z)[0],
                                      np.asarray(d.to_canonical(sd).Z))
        print('OK mesh 1xP == shardmap')
    """, n_devices=4)
    assert "OK mesh 1xP == shardmap" in out


# ---------------------------------------------------------------------------
# driver="mesh" end to end + checkpoint interchange across all four drivers
# ---------------------------------------------------------------------------

def test_mesh_driver_runs_and_interchanges_checkpoints():
    """driver='mesh' (2 chains x 2 data shards on 4 forced host devices)
    runs end to end through MCMCDriver, reports chain-axis diagnostics in
    eval records, and its checkpoints restore under driver='multichain'
    and back (chain count preserved)."""
    out = run_with_devices("""
        import dataclasses, math, tempfile
        from repro.core.ibp import IBPHypers
        from repro.data import cambridge_data
        from repro.runtime import DriverConfig, MCMCDriver
        X, _, _ = cambridge_data(N=48, sigma_n=0.4, seed=3)
        d = tempfile.mkdtemp()
        cfg = DriverConfig(P=2, K_max=12, K_tail=6, L=2, n_iters=16,
                           ckpt_every=8, eval_every=16, driver='mesh',
                           n_chains=2, stale_sync=1, ckpt_dir=d)
        drv = MCMCDriver(X, cfg, IBPHypers())
        gs, ss = drv.run()
        assert ss.Z.shape[0] == 2, ss.Z.shape      # chain axis preserved
        rec = drv.history[-1]
        assert 'sigma_x_rhat' in rec and len(rec['K_chains']) == 2
        assert math.isfinite(rec['sigma_x_rhat'])
        # mesh checkpoint -> multichain (elastic P too: 2 -> 4 data shards)
        cfg_mc = dataclasses.replace(cfg, driver='multichain', P=4,
                                     n_iters=20)
        gs2, ss2 = MCMCDriver(X, cfg_mc, IBPHypers()).run()
        assert int(gs2.it.max()) == 20 and ss2.Z.shape[0] == 2
        # multichain checkpoint -> mesh
        cfg_m2 = dataclasses.replace(cfg, n_iters=24)
        gs3, ss3 = MCMCDriver(X, cfg_m2, IBPHypers()).run()
        assert int(gs3.it.max()) == 24 and ss3.Z.shape[0] == 2
        # changing the chain count across a restart still fails loudly
        try:
            MCMCDriver(X, dataclasses.replace(cfg, n_chains=3, P=1,
                                              n_iters=30),
                       IBPHypers()).run()
            raise SystemExit('expected chain-count mismatch to raise')
        except ValueError as e:
            assert 'n_chains' in str(e)
        print('OK mesh driver + ckpt interchange')
    """, n_devices=4)
    assert "OK mesh driver + ckpt interchange" in out


def test_checkpoint_interchange_chainless_drivers(tmp_path):
    """vmap-written checkpoints restore under shardmap and back (the
    chainless half of the four-driver interchange; P=1 mesh runs
    in-process on the single CPU device)."""
    X, _, _ = cambridge_data(N=24, sigma_n=0.4, seed=5)
    mk = lambda driver, n: DriverConfig(
        P=1, K_max=12, K_tail=4, L=2, n_iters=n, ckpt_every=4,
        eval_every=100, driver=driver, ckpt_dir=str(tmp_path))
    MCMCDriver(X, mk("vmap", 4), IBPHypers()).run()
    gs, ss = MCMCDriver(X, mk("shardmap", 8), IBPHypers()).run()
    assert int(gs.it) == 8
    gs2, ss2 = MCMCDriver(X, mk("vmap", 12), IBPHypers()).run()
    assert int(gs2.it) == 12 and ss2.Z.shape == ss.Z.shape


def test_stale_sync_validation_rejects_negative():
    """The satellite fix: stale_sync=-1 used to silently skip the stale
    loop; overflow_every=0 used to crash with a bare ZeroDivisionError in
    run(). Both are rejected at config time now, through both surfaces."""
    with pytest.raises(ValueError, match="stale_sync"):
        SamplerSpec(stale_sync=-1)
    with pytest.raises(ValueError, match="stale_sync"):
        as_spec(DriverConfig(stale_sync=-1))
    with pytest.raises(ValueError, match="overflow_every"):
        SamplerSpec(overflow_every=0)
    with pytest.raises(ValueError, match="overflow_every"):
        as_spec(DriverConfig(overflow_every=0))
