"""Multi-device tests, run in subprocesses so the main pytest process keeps a
single CPU device (the dry-run contract: only dryrun.py forces many devices)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_shardmap_hybrid_runs_and_converges():
    out = run_with_devices("""
        import jax
        from repro.data import cambridge_data
        from repro.core.ibp import IBPHypers, SamplerSpec, build_sampler
        X, _, _ = cambridge_data(N=96, seed=1)
        spec = SamplerSpec(P=8, K_max=16, K_tail=6, K_init=4, L=5,
                           data='shardmap')
        s = build_sampler(spec, IBPHypers(), X)
        gs, st = s.init(jax.random.key(1))
        for _ in range(40):
            gs, st = s.step(gs, st)
        K = int(gs.active.sum()); sx = float(gs.sigma_x)
        assert 3 <= K <= 9, K
        assert 0.3 <= sx <= 0.75, sx
        print('OK', K, sx)
    """)
    assert "OK" in out


def test_shardmap_matches_vmap_semantics():
    """The shard_map layout and the vmap layout produce identical states
    under identical keys (they implement the same algorithm), starting
    from the same canonical state."""
    out = run_with_devices("""
        import numpy as np, jax
        from repro.data import cambridge_data
        from repro.core.ibp import IBPHypers, SamplerSpec, build_sampler
        X, _, _ = cambridge_data(N=32, seed=4)
        hyp = IBPHypers()
        spec = SamplerSpec(P=4, K_max=12, K_tail=4, K_init=3, L=2)
        sv = build_sampler(spec, hyp, X)
        sm = build_sampler(spec.replace(data='shardmap'), hyp, X)
        gs_v, st_v = sv.init(jax.random.key(2))
        gs_s = gs_v
        st_s = sm.from_canonical(sv.to_canonical(st_v))
        for _ in range(5):
            gs_v, st_v = sv.step(gs_v, st_v)
            gs_s, st_s = sm.step(gs_s, st_s)
        np.testing.assert_array_equal(
            np.asarray(sv.to_canonical(st_v).Z),
            np.asarray(sm.to_canonical(st_s).Z))
        # float scalars agree up to reduction-ordering ULPs (psum vs axis-sum)
        np.testing.assert_allclose(float(gs_v.sigma_x), float(gs_s.sigma_x),
                                   rtol=1e-5)
        np.testing.assert_allclose(float(gs_v.sigma_a), float(gs_s.sigma_a),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(gs_v.A), np.asarray(gs_s.A),
                                   atol=1e-5)
        assert int(gs_v.p_prime) == int(gs_s.p_prime)
        print('OK identical')
    """)
    assert "OK identical" in out


def test_fused_sync_matches_staged():
    """The fused single-all-reduce master sync (SSE via the trace identity,
    tail mask folded into the stats payload) computes the same iteration as
    the staged 3-all-reduce schedule, up to reduction-order ULPs."""
    out = run_with_devices("""
        import numpy as np, jax
        from repro.data import cambridge_data
        from repro.core.ibp import IBPHypers, SamplerSpec, build_sampler
        X, _, _ = cambridge_data(N=64, seed=9)
        hyp = IBPHypers()
        outs = {}
        for sync in ('staged', 'fused'):
            spec = SamplerSpec(P=4, K_max=12, K_tail=4, K_init=3, L=2,
                               data='shardmap', sync=sync)
            s = build_sampler(spec, hyp, X)
            gs, st = s.init(jax.random.key(3))
            for _ in range(3):
                gs, st = s.step(gs, st)
                jax.block_until_ready(st[0])
            outs[sync] = (np.asarray(st[0]), np.asarray(gs.A),
                          float(gs.sigma_x), np.asarray(gs.active))
        np.testing.assert_array_equal(outs['staged'][0], outs['fused'][0])
        np.testing.assert_allclose(outs['staged'][1], outs['fused'][1],
                                   atol=1e-4)
        np.testing.assert_allclose(outs['staged'][2], outs['fused'][2],
                                   rtol=1e-4)
        np.testing.assert_array_equal(outs['staged'][3], outs['fused'][3])
        print('OK fused == staged')
    """, n_devices=4)
    assert "OK fused == staged" in out


def test_shardmap_step_carries_scopes():
    """Both shard_map sync schedules compile with the step's three named
    scopes (DESIGN.md §16); each all-reduce lies inside ``ibp_sync``, in
    the nested scope of its collective: ``ar_tail``, ``ar_stats`` and
    ``ar_sse`` for the staged sync, ``ar_payload`` for the fused one."""
    out = run_with_devices("""
        import re, jax
        from repro.data import cambridge_data
        from repro.core.ibp import IBPHypers, SamplerSpec, build_sampler
        X, _, _ = cambridge_data(N=64, seed=9)
        nested = {'staged': {'ar_tail', 'ar_stats', 'ar_sse'},
                  'fused': {'ar_payload'}}
        for sync in ('staged', 'fused'):
            spec = SamplerSpec(P=4, K_max=12, K_tail=4, K_init=3, L=2,
                               data='shardmap', sync=sync)
            s = build_sampler(spec, IBPHypers(), X)
            gs, st = s.init(jax.random.key(3))
            hlo = s._fns.step.lower(s._Xn, gs, *st).compile().as_text()
            names = re.findall(r'op_name="([^"]*)"', hlo)
            for scope in ('ibp_sweep', 'ibp_tail', 'ibp_sync'):
                assert any(scope + '/' in n for n in names), (sync, scope)
            ars = re.findall(
                r'= [^\\n]*? all-reduce\\([^\\n]*?op_name="([^"]*)"', hlo)
            assert ars and all('ibp_sync/' in n for n in ars), (sync, ars)
            scopes = [re.search(r'ibp_sync/(ar_[a-z]+)/', n) for n in ars]
            assert all(scopes), (sync, ars)
            assert {m.group(1) for m in scopes} == nested[sync], (sync, ars)
            print('OK', sync, len(ars))
    """, n_devices=4)
    assert "OK staged" in out and "OK fused" in out


def test_shardmap_data_placed_per_device():
    """Under data='shardmap' each device receives its own rows straight
    from host memory and the sampler keeps no single-device array of X's
    size; the initial state and three steps are bitwise those of the
    earlier placement (X gathered on the default device, ``init`` run on
    that copy), and a state from ``init`` runs the one compiled step."""
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as PS
        from repro.data import cambridge_data
        from repro.core.ibp import IBPHypers, SamplerSpec, build_sampler
        from repro.core.ibp.hybrid import init_hybrid
        X, _, _ = cambridge_data(N=400, seed=5)
        spec = SamplerSpec(P=4, K_max=12, K_tail=4, K_init=4, L=2,
                           data='shardmap')
        s = build_sampler(spec, IBPHypers(), X)
        N, D = s.N, s.D
        for name, v in vars(s).items():
            if isinstance(v, jax.Array) and v.size >= N * D:
                assert len(v.sharding.device_set) == 4, (name, v.sharding)
        assert {sh.data.shape for sh in s._Xn.addressable_shards} == {
            (N // 4, D)}
        compiles = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda ev, secs, **k: compiles.append(ev)
            if ev == '/jax/core/compile/backend_compile_duration' else None)
        key = jax.random.key(11)
        states = [s.init(key)]
        n0 = len(compiles)
        for _ in range(3):
            states.append(s.step(*states[-1]))
        jax.block_until_ready(states[-1])
        assert len(compiles) - n0 == 1, compiles[n0:]    # the step, once
        # the earlier placement, composed from the same building blocks
        Xs_old = jnp.asarray(s.X_global.reshape(4, N // 4, D))
        gs, ss = init_hybrid(key, Xs_old, spec.K_max, K_tail=spec.K_tail,
                             alpha=spec.alpha, sigma_x=spec.sigma_x,
                             sigma_a=spec.sigma_a, K_init=spec.K_init)
        st = s.from_canonical(ss)
        Xn_old = jax.device_put(jnp.asarray(s.X_global),
                                NamedSharding(s.mesh, PS('data')))
        for i, new in enumerate(states):
            if i:
                gs, *st = s._fns.step(Xn_old, gs, *st)
            for x, y in zip(jax.tree.leaves(new), jax.tree.leaves((gs, st))):
                if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
                    x, y = jax.random.key_data(x), jax.random.key_data(y)
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        print('OK placed')
    """, n_devices=4)
    assert "OK placed" in out


def test_moe_a2a_matches_gather_dispatch():
    """The shard_map all-to-all MoE dispatch computes the same function as
    the global-capacity gather baseline when nothing drops (capacity_factor
    large): same forward output, same aux loss, on a (data=2, model=2) mesh."""
    out = run_with_devices("""
        import dataclasses, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax.sharding import AxisType
        from jax import make_mesh, set_mesh
        from repro.configs import get_config
        from repro.models import init_model, ActSpecs
        from repro.models.moe import moe_apply
        from repro.parallel.mesh import act_specs

        cfg = get_config('phi3.5-moe-42b-a6.6b', smoke=True)
        cfg = dataclasses.replace(cfg, n_experts=8, top_k=2, d_model=32,
                                  d_ff_expert=16, capacity_factor=8.0,
                                  n_shared_experts=1)
        from repro.models.moe import moe_init
        p, _ = moe_init(jax.random.key(0), cfg)
        x = jax.random.normal(jax.random.key(1), (4, 8, 32), jnp.float32)

        # reference: single-device gather dispatch
        cfg_g = dataclasses.replace(cfg, moe_impl='gather')
        y_ref, aux_ref = moe_apply(p, x, cfg_g)

        mesh = make_mesh((2, 2), ('data', 'model'),
                         axis_types=(AxisType.Auto,) * 2)
        with set_mesh(mesh):
            specs = act_specs(mesh, seq_len=8, batch=4, mode='train')
            cfg_a = dataclasses.replace(cfg, moe_impl='a2a')
            y_a2a, aux_a2a = jax.jit(
                lambda p, x: moe_apply(p, x, cfg_a, specs=specs)
            )(p, x)
        np.testing.assert_allclose(np.asarray(y_a2a), np.asarray(y_ref),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(float(aux_a2a), float(aux_ref), rtol=1e-5)

        # and it differentiates (grads flow through both all_to_alls)
        def loss(p, x):
            y, aux = moe_apply(p, x, cfg_a, specs=specs)
            return jnp.sum(y * y) + 0.01 * aux
        with set_mesh(mesh):
            g = jax.jit(jax.grad(loss))(p, x)
        assert all(np.all(np.isfinite(v)) for v in jax.tree.leaves(
            jax.tree.map(np.asarray, g)))
        gn = float(jnp.linalg.norm(g['wi']))
        assert gn > 0, gn
        print('OK a2a == gather, grad norm', gn)
    """, n_devices=4)
    assert "OK a2a == gather" in out


def test_lm_train_step_shards_on_8_devices():
    """A reduced LM train step pjit-shards over a (4, 2) data x model mesh."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        from jax import make_mesh, set_mesh
        from repro.configs import get_config
        from repro.models import init_model, make_train_step
        from repro.models.transformer import ActSpecs
        from repro.optim import AdamW
        from repro.parallel.mesh import (act_specs, batch_specs, named,
                                         resolve_param_specs)
        import dataclasses
        cfg = get_config('granite-3-8b', smoke=True)
        cfg = dataclasses.replace(cfg, d_model=64, n_heads=4, n_kv_heads=2,
                                  d_ff=128)
        mesh = make_mesh((4, 2), ('data', 'model'),
                         axis_types=(AxisType.Auto,) * 2)
        with set_mesh(mesh):
            holder = {}
            def build(k):
                p, s = init_model(k, cfg)
                holder['s'] = s
                return p
            params = build(jax.random.key(0))
            pspec = resolve_param_specs(holder['s'], params, mesh, mode='train')
            p_sh = named(mesh, pspec)
            params = jax.device_put(params, p_sh)
            opt = AdamW(lr=1e-3)
            ost = opt.init(params)
            batch = {'tokens': jnp.zeros((8, 32), jnp.int32) + 5}
            specs = act_specs(mesh, seq_len=32, batch=8, mode='train')
            step = jax.jit(make_train_step(cfg, opt, specs))
            p2, o2, m = step(params, ost, batch)
            assert np.isfinite(float(m['loss']))
            # a TP-sharded weight is actually distributed
            w = p2['layers']['attn']['wq']
            assert len(w.sharding.device_set) > 1
            print('OK sharded loss', float(m['loss']))
    """)
    assert "OK sharded" in out


def test_driver_shardmap_backend_selectable():
    """MCMCDriver with driver='shardmap' runs the production collective path
    end to end (checkpointing included) on 8 forced host devices, and its
    checkpoints remain interchangeable with the vmap backend."""
    out = run_with_devices("""
        import dataclasses, tempfile, numpy as np
        from repro.core.ibp import IBPHypers
        from repro.data import cambridge_data
        from repro.runtime import DriverConfig, MCMCDriver
        X, _, _ = cambridge_data(N=96, seed=5)
        d = tempfile.mkdtemp()
        cfg = DriverConfig(P=8, K_max=16, K_tail=6, L=3, n_iters=20,
                           ckpt_every=10, eval_every=10, driver='shardmap',
                           stale_sync=1, ckpt_dir=d)
        drv = MCMCDriver(X, cfg, IBPHypers())
        gs, ss = drv.run()
        K = int(gs.active.sum()); sx = float(gs.sigma_x)
        assert 2 <= K <= 10, K
        assert 0.3 <= sx <= 0.8, sx
        assert ss.Z.shape[0] == 8
        assert 'sigma_x_rhat' in drv.history[-1]
        # same checkpoint resumes on the vmap backend (elastic P too)
        cfg_v = dataclasses.replace(cfg, driver='vmap', P=4, n_iters=25)
        gs2, ss2 = MCMCDriver(X, cfg_v, IBPHypers()).run()
        assert int(gs2.it) == 25 and ss2.Z.shape[0] == 4
        print('OK shardmap driver', K, sx)
    """)
    assert "OK shardmap driver" in out
