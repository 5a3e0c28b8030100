"""The data-vmap layouts run the collapsed tail once per sub-iteration,
unbatched, on p''s slice (DESIGN.md §12).

The oracle below is the composition those layouts ran before: every
shard's L sub-iterations vmapped over the shard axis (the tail's
``cond`` on p' then lowers to a select that runs the tail on every
shard), followed by the same master sync. On XLA-CPU the two must agree
bit for bit: the same keys, the same row chain on p', the same sweeps.
"""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ibp import IBPHypers, SamplerSpec, build_sampler
from repro.core.ibp import collapsed, hybrid

N_ITERS = 8
# a small paper-like instance: P=5 shards of 20 rows of six strong
# planted features, started from one feature with sigma_x held at the
# noise so that the tail has births to make, and a refresh cadence short
# enough that its branch is taken
SPEC = dict(P=5, L=2, K_max=12, K_tail=4, K_init=1, alpha=3.0,
            sigma_x=0.3, sigma_a=3.0, collapsed_backend="fast",
            chol_refresh=8)
HYP = IBPHypers(resample_sigmas=False, resample_alpha=False)


@pytest.fixture(scope="module")
def X():
    rng = np.random.default_rng(0)
    Zt = (rng.random((100, 6)) < 0.5).astype(np.float32)
    At = 3.0 * rng.standard_normal((6, 36)).astype(np.float32)
    return Zt @ At + 0.3 * rng.standard_normal((100, 36)).astype(np.float32)


def _oracle_sub(Xs, gs, ss, spec, N_g):
    """The pre-change sub-iterations: ``shard_sub_iterations`` vmapped
    over the P simulated shards."""
    sub = partial(
        hybrid.shard_sub_iterations, N_global=N_g, L=spec.L,
        backend=spec.backend,
        row_scan=collapsed.tail_row_scan(spec.collapsed_backend,
                                         spec.chol_refresh),
    )
    return jax.vmap(sub, in_axes=(0, 0, 0, 0, None, 0))(
        Xs, ss.Z, ss.Z_tail, ss.tail_active, gs, jnp.arange(Xs.shape[0]))


def _oracle_fns(spec, hyp, N_g):
    def step(Xs, gs, ss):
        Z, Zt, ta, n_sat, n_ref = _oracle_sub(Xs, gs, ss, spec, N_g)
        return hybrid._vmap_sync(Xs, gs, Z, Zt, ta, jnp.sum(n_sat),
                                 jnp.sum(n_ref), hyp, N_g)

    def stale(Xs, gs, ss):
        gs_sweep = dataclasses.replace(gs,
                                       key=jax.random.fold_in(gs.key, 13))
        Z, Zt, ta, _, _ = _oracle_sub(Xs, gs_sweep, ss, spec, N_g)
        gs_out = dataclasses.replace(gs, key=jax.random.fold_in(gs.key, 14))
        return gs_out, hybrid.HybridShard(Z=Z, Z_tail=Zt, tail_active=ta)

    if spec.chains == "vmap":
        step = jax.vmap(step, in_axes=(None, 0, 0))
        stale = jax.vmap(stale, in_axes=(None, 0, 0))
    return jax.jit(step), jax.jit(stale)


def _leaves(gs, ss):
    out = {f"gs.{f.name}": getattr(gs, f.name)
           for f in dataclasses.fields(gs)}
    out["gs.key"] = jax.random.key_data(gs.key)
    out.update({"Z": ss.Z, "Z_tail": ss.Z_tail,
                "tail_active": ss.tail_active})
    return {k: np.asarray(v) for k, v in out.items()}


@pytest.mark.parametrize("chains", ["none", "vmap"])
@pytest.mark.parametrize("collapsed_backend", ["ref", "fast"])
@pytest.mark.parametrize("fn", ["step", "stale"])
def test_vmap_layout_matches_batched_tail_bitwise(X, fn, collapsed_backend,
                                                  chains):
    """``stale`` runs a stale pass before every step, so the pass's own
    tail (kept, not cleared) is compared and then promoted by the step."""
    kw = dict(SPEC, collapsed_backend=collapsed_backend, chains=chains)
    if chains == "vmap":
        kw["n_chains"] = 2
    spec = SamplerSpec(**kw)
    s = build_sampler(spec, HYP, X)
    o_step, o_stale = _oracle_fns(spec, HYP, float(s.N))
    gs, st = s.init(jax.random.key(7))
    pps, births = [], 0
    for it in range(N_ITERS):
        if fn == "stale":
            new = s.stale(gs, st)
            old = o_stale(s._Xn, gs, st)
            a, b = _leaves(*new), _leaves(*old)
            for k in a:
                np.testing.assert_array_equal(a[k], b[k], err_msg=(it, k))
            gs, st = new
        pps.append(np.asarray(gs.p_prime))
        k_before = np.asarray(jnp.sum(gs.active, axis=-1))
        new = s.step(gs, st)
        old = o_step(s._Xn, gs, st)
        a, b = _leaves(*new), _leaves(*old)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k], err_msg=(it, k))
        gs, st = new
        births += int(np.sum(np.asarray(jnp.sum(gs.active, axis=-1))
                             > k_before))
    # the trajectory exercised what the change touches: a tail on a
    # shard other than 0, births promoted out of it, and refreshes of
    # the fast carry (the ref oracle factorizes every row afresh)
    assert np.any(np.concatenate([np.ravel(p) for p in pps]) != 0)
    assert births > 0
    assert np.all(np.asarray(gs.tail_refresh) > 0) == (
        collapsed_backend == "fast")
