"""The paper's hybrid parallel MCMC sampler for the IBP.

One global iteration (paper Sec. 3 pseudocode):

  for l = 1..L sub-iterations:
      every shard p:   uncollapsed Gibbs sweep of Z over the K+ instantiated
                       features given (pi, A)                  [data-parallel]
      shard p' only:   collapsed Gibbs on its local tail features (A* integrated
                       out, residual R = X_p - Z A as data, global-N priors)
                       + MH birth of K_new ~ Poisson(alpha/N) per row
  master sync:
      psum tail mask -> promote p''s tail columns into free K+ slots
      psum (m, ZtZ, ZtX) -> deactivate dead columns, draw A | Z,X then
      pi_k ~ Beta(m_k, 1 + N - m_k)
      psum ||X - Z A||^2 -> sigma_x^2, then sigma_a^2, alpha ~ conjugates
      p' ~ Uniform{0..P-1}; clear tail

Deviation from the paper (recorded in DESIGN.md §4): the master is
*replicated* — every shard all-reduces the same sufficient statistics and
draws identical posteriors from a shared PRNG key, so the paper's explicit
gather -> master-compute -> broadcast round becomes a single all-reduce.
The draws are bitwise identical across shards, hence semantically the same
algorithm with strictly less communication.

Exactness note: on p', the instantiated-feature sweep conditions on A+ only
(tail contribution not subtracted), exactly as written in the paper's
pseudocode; the tail sampler sees R = X_p - Z A+ as its data.

Parallelism is expressed as two ORTHOGONAL axes, not a driver enum
(DESIGN.md §13): ``spec.chains`` picks the chain layout (``none`` — no
chain axis; ``vmap`` — C chains vmapped over the full iteration;
``mesh`` — C chains as a real mesh axis) and ``spec.data`` picks the
data layout (``vmap`` — P shards simulated by vmap, psum == sum over
the shard axis; ``shardmap`` — shard_map over a mesh data axis, psum ==
jax.lax.psum, the production path). ``build_hybrid_fns(spec, hyp, ...)``
is the ONE construction entry point: it reads the kernel knobs
(``L``, ``backend``, ``sync``) off the spec, takes the tail's row scan
once from ``collapsed.tail_row_scan`` and returns jitted ``(step,
stale)`` functions for the requested layout — the old per-backend
entry points (``hybrid_iteration_vmap`` / ``_multichain`` /
``hybrid_stale_pass`` / ``make_hybrid_iteration_shardmap``) are
subsumed by spec layouts.

The ``stale`` function is the bounded-staleness knob (DESIGN.md §10):
sub-iterations only, no master sync (and, on a mesh, no collectives at
all) — explicitly non-exact.

Every layout runs the step's three layers under named scopes, so a
profiler trace can split the step (DESIGN.md §16): ``ibp_sweep`` (the
uncollapsed sweep, ``sweeps.uncollapsed_sweep``), ``ibp_tail`` (the
collapsed tail, ``_tail_sub_iteration``) and ``ibp_sync`` (everything
after the sub-iterations: promotion, statistics, the master's draws).

Most callers want the higher-level ``build_sampler`` (core/ibp/api.py),
which wraps these functions in a uniform init/step/stale/to_canonical
protocol and owns mesh creation + data placement.
"""
from __future__ import annotations

import dataclasses
from collections.abc import Callable
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import math as ibm
from .collapsed import tail_row_scan
from .sweeps import uncollapsed_sweep

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HybridGlobal:
    """Replicated across shards."""

    A: Array         # (K_max, D)
    pi: Array        # (K_max,)
    active: Array    # (K_max,)
    alpha: Array     # ()
    sigma_x: Array   # ()
    sigma_a: Array   # ()
    key: Array       # PRNG key (shared)
    p_prime: Array   # () int32
    it: Array        # () int32
    overflow: Array  # () int32 — promoted-feature drops due to K_max capacity
    tail_sat: Array  # () int32 — tail rows whose accepted MH birth was
    #                  vetoed by K_tail capacity (drives adaptive K_tail
    #                  growth at the driver's restart boundary)
    tail_refresh: Array  # () int32 — tail rows that took the exact
    #                      refactorization (cadence + drift monitor)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HybridShard:
    """Sharded along the observation axis. Leading axis = shard (size P)."""

    Z: Array            # (P, N_p, K_max)
    Z_tail: Array       # (P, N_p, K_tail)
    tail_active: Array  # (P, K_tail)


def init_hybrid(
    key: Array,
    X_shards: Array,  # (P, N_p, D)
    K_max: int,
    K_tail: int = 8,
    alpha: float = 3.0,
    sigma_x: float = 1.0,
    sigma_a: float = 1.0,
    K_init: int = 4,
    init_from_data: bool = True,
) -> tuple[HybridGlobal, HybridShard]:
    P_, N_p, D = X_shards.shape
    dtype = X_shards.dtype
    K_init = min(K_init, K_max)
    k0, k1, k2 = jax.random.split(key, 3)
    Z = jnp.zeros((P_, N_p, K_max), dtype)
    if K_init > 0:
        Z = Z.at[:, :, :K_init].set(
            jax.random.bernoulli(k0, 0.5, (P_, N_p, K_init)).astype(dtype)
        )
    A = jnp.zeros((K_max, D), dtype)
    if K_init > 0:
        if init_from_data:
            # seed features with (noised) data rows spread across shards —
            # avoids the all-features-die nucleation trap at cold start
            flat = X_shards.reshape(-1, D)
            stride = max(1, flat.shape[0] // K_init)
            seeds = flat[::stride][:K_init]
            A = A.at[:K_init].set(
                seeds + 0.1 * jax.random.normal(k1, seeds.shape, dtype)
            )
        else:
            A = A.at[:K_init].set(
                jax.random.normal(k1, (K_init, D), dtype) * sigma_a
            )
    active = jnp.zeros((K_max,), dtype).at[:K_init].set(1.0)
    gs = HybridGlobal(
        A=A,
        pi=jnp.zeros((K_max,), dtype).at[:K_init].set(0.5),
        active=active,
        alpha=jnp.asarray(alpha, dtype),
        sigma_x=jnp.asarray(sigma_x, dtype),
        sigma_a=jnp.asarray(sigma_a, dtype),
        key=k2,
        p_prime=jnp.asarray(0, jnp.int32),
        it=jnp.asarray(0, jnp.int32),
        overflow=jnp.asarray(0, jnp.int32),
        tail_sat=jnp.asarray(0, jnp.int32),
        tail_refresh=jnp.asarray(0, jnp.int32),
    )
    ss = HybridShard(
        Z=Z,
        Z_tail=jnp.zeros((P_, N_p, K_tail), dtype),
        tail_active=jnp.zeros((P_, K_tail), dtype),
    )
    return gs, ss


# --------------------------------------------------------------------------
# per-shard kernels (unbatched: no leading P axis)
# --------------------------------------------------------------------------


def _tail_sub_iteration(
    X_p: Array,
    Z: Array,
    Z_tail: Array,
    tail_active: Array,
    gs: HybridGlobal,
    N_global: float,
    key: Array,
    row_scan: Callable,
) -> tuple[Array, Array, Array, Array]:
    """Collapsed Gibbs + MH births on the tail (runs on p' only).

    ``row_scan`` is the tail's row scan from ``collapsed.tail_row_scan``
    (DESIGN.md §12): on a TPU with the fast backend the whole scan is
    one kernel launch (``collapsed.fused_tail_scan``), elsewhere the XLA
    scan of the carried factor (``collapsed.collapsed_row_scan``).

    Returns (Z_tail, tail_active, n_sat, n_refresh): ``n_sat`` counts
    rows whose accepted MH birth was vetoed purely by K_tail capacity —
    the tail-saturation signal driving adaptive K_tail growth;
    ``n_refresh`` counts rows that took the exact refactorization.
    """
    with jax.named_scope("ibp_tail"):
        # residual given instantiated features = the tail model's data
        R = X_p - (Z * gs.active[None, :]) @ gs.A
        m_t = jnp.sum(Z_tail, axis=0)
        ZtZ_t = Z_tail.T @ Z_tail
        ZtR = Z_tail.T @ R
        Z_tail, tail_active, _, _, m_t, n_refresh, n_sat = row_scan(
            Z_tail, tail_active, ZtZ_t, ZtR, m_t, R, key,
            gs.alpha, gs.sigma_x, gs.sigma_a, N=N_global)
        # prune dead tail columns
        tail_active = tail_active * (m_t > 0.5)
        Z_tail = Z_tail * tail_active[None, :]
    return Z_tail, tail_active, n_sat, n_refresh


def _sub_keys(key_shard: Array, l: Array) -> Array:
    """Keys of sub-iteration ``l`` on the shard whose key is ``key_shard``
    = ``fold_in(gs.key, shard)``: (2,) keys, the sweep's then the tail's.
    Every layout derives them here, so all draw the same numbers."""
    return jax.random.split(jax.random.fold_in(key_shard, l))


def shard_sub_iterations(
    X_p: Array,
    Z: Array,
    Z_tail: Array,
    tail_active: Array,
    gs: HybridGlobal,
    shard_idx: Array,
    N_global: float,
    L: int,
    backend: str,
    row_scan: Callable,
) -> tuple[Array, Array, Array, Array, Array]:
    """L sub-iterations of the paper's inner loop on one shard (the
    shard_map layouts: the shard is this device, so the ``cond`` on p'
    is a real branch).

    Returns (Z, Z_tail, tail_active, n_sat, n_refresh) — the tail's
    counts summed over this shard's sub-iterations (nonzero only on p').
    """
    key_shard = jax.random.fold_in(gs.key, shard_idx)
    is_pprime = shard_idx == gs.p_prime

    def one(l, carry):
        Z, Z_tail, tail_active, n_sat, n_ref = carry
        ku, kt = _sub_keys(key_shard, l)
        Z = uncollapsed_sweep(
            X_p, Z, gs.A, gs.pi, gs.active, gs.sigma_x, ku, backend=backend
        )

        def with_tail(args):
            Z_tail, tail_active, n_sat, n_ref = args
            Z_tail, tail_active, sat, ref = _tail_sub_iteration(
                X_p, Z, Z_tail, tail_active, gs, N_global, kt, row_scan)
            return Z_tail, tail_active, n_sat + sat, n_ref + ref

        Z_tail, tail_active, n_sat, n_ref = jax.lax.cond(
            is_pprime, with_tail, lambda a: a,
            (Z_tail, tail_active, n_sat, n_ref),
        )
        return Z, Z_tail, tail_active, n_sat, n_ref

    zero = jnp.zeros((), jnp.int32)
    return jax.lax.fori_loop(
        0, L, one, (Z, Z_tail, tail_active, zero, zero)
    )


def _vmap_sub_iterations(
    X_shards: Array,
    Z: Array,
    Z_tail: Array,
    tail_active: Array,
    gs: HybridGlobal,
    N_global: float,
    L: int,
    backend: str,
    row_scan: Callable,
) -> tuple[Array, Array, Array, Array, Array]:
    """L sub-iterations of the paper's inner loop over the P simulated
    shards (the data-vmap layouts; leading shard axis on X_shards, Z,
    Z_tail, tail_active).

    Sub-iteration l sweeps every shard, vmapped, then runs the tail once,
    unbatched, on p''s slice and writes it back at p'; the other shards'
    tail state is left as it is. Batched over shards, the tail's
    ``lax.cond``s would lower to selects that run both branches on every
    row (the exact refactorization included) for all P shards (DESIGN.md
    §12). Returns (Z, Z_tail, tail_active, n_sat, n_refresh).
    """
    pp = gs.p_prime
    key_shards = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        gs.key, jnp.arange(X_shards.shape[0]))
    X_pp = X_shards[pp]
    sweep = jax.vmap(partial(uncollapsed_sweep, backend=backend),
                     in_axes=(0, 0, None, None, None, None, 0))

    def one(l, carry):
        Z, Z_tail, tail_active, n_sat, n_ref = carry
        keys = jax.vmap(_sub_keys, in_axes=(0, None))(key_shards, l)
        # the scope outside the vmap keeps ``ibp_sweep`` a component of
        # its own in the op_name (inside, it reads ``vmap(ibp_sweep)``)
        with jax.named_scope("ibp_sweep"):
            Z = sweep(X_shards, Z, gs.A, gs.pi, gs.active, gs.sigma_x,
                      keys[:, 0])
        Zt, ta, sat, ref = _tail_sub_iteration(
            X_pp, Z[pp], Z_tail[pp], tail_active[pp], gs, N_global,
            keys[pp, 1], row_scan,
        )
        return (Z, Z_tail.at[pp].set(Zt), tail_active.at[pp].set(ta),
                n_sat + sat, n_ref + ref)

    zero = jnp.zeros((), jnp.int32)
    return jax.lax.fori_loop(
        0, L, one, (Z, Z_tail, tail_active, zero, zero)
    )


def promote_tail(
    Z: Array,
    Z_tail: Array,
    tail_active_g: Array,
    active: Array,
) -> tuple[Array, Array, Array]:
    """Scatter tail columns into free K+ slots (identical on every shard).

    ``tail_active_g`` is the globally-reduced tail mask (only p' contributes),
    so every shard computes the same slot assignment. Shards other than p'
    scatter zero columns. Returns (Z_new, active_new, n_dropped).
    """
    K_max = Z.shape[1]
    free = 1.0 - active
    n_free = jnp.sum(free)
    rank = jnp.cumsum(tail_active_g) * tail_active_g        # 1-indexed among tails
    kept = tail_active_g * (rank <= n_free)
    n_drop = jnp.sum(tail_active_g) - jnp.sum(kept)
    # target slot of tail j = index of the rank_j-th free slot
    # searchsorted over cumsum(free) gives that index
    cums = jnp.cumsum(free)
    tgt = jnp.searchsorted(cums, jnp.maximum(rank, 1.0))    # (K_tail,)
    tgt = jnp.clip(tgt, 0, K_max - 1).astype(jnp.int32)
    cols = Z_tail * kept[None, :]
    Z_new = Z.at[:, tgt].add(cols)                          # zero cols are no-ops
    active_new = active.at[tgt].max(kept)
    return Z_new, active_new, n_drop.astype(jnp.int32)


def local_stats(X_p: Array, Z: Array) -> dict[str, Array]:
    return {
        "m": jnp.sum(Z, axis=0),
        "ZtZ": Z.T @ Z,
        "ZtX": Z.T @ X_p,
    }


def local_sse(X_p: Array, Z: Array, A: Array, active: Array) -> Array:
    R = X_p - (Z * active[None, :]) @ A
    return jnp.sum(R * R)


def master_step1(
    stats: dict[str, Array],
    active: Array,
    gs: HybridGlobal,
    N_global: float,
    D: int,
) -> tuple[Array, Array, Array, Array]:
    """Deaths, A | Z,X draw, pi | Z draw — identical on every shard."""
    key = gs.key
    k_a, k_pi = jax.random.split(jax.random.fold_in(key, 101))
    m = stats["m"] * active
    active = active * (m > 0.5)
    mask2 = ibm.mask_outer(active)
    ZtZ = stats["ZtZ"] * mask2
    ZtX = stats["ZtX"] * active[:, None]
    A = ibm.a_posterior_draw(k_a, ZtZ, ZtX, active, gs.sigma_x, gs.sigma_a)
    # pi_k | Z ~ Beta(m_k, 1 + N - m_k) for instantiated features
    a_beta = jnp.maximum(m, 1e-6)
    b_beta = 1.0 + N_global - m
    pi = jax.random.beta(k_pi, a_beta, b_beta) * active
    return A, pi, active, m


def master_step2(
    sse: Array,
    A: Array,
    active: Array,
    gs: HybridGlobal,
    hyp,
    N_global: float,
    D: int,
    P_: int,
) -> tuple[Array, Array, Array, Array]:
    """sigma_x, sigma_a, alpha, p' — identical on every shard."""
    k_sx, k_sa, k_al, k_pp = jax.random.split(jax.random.fold_in(gs.key, 202), 4)
    k_plus = jnp.sum(active)
    if hyp.resample_sigmas:
        sx2 = ibm.inverse_gamma_draw(
            k_sx, hyp.a_sx + 0.5 * N_global * D, hyp.b_sx + 0.5 * sse
        )
        sigma_x = jnp.sqrt(sx2)
        a_ss = jnp.sum(A * A * active[:, None])
        sa2 = ibm.inverse_gamma_draw(
            k_sa, hyp.a_sa + 0.5 * k_plus * D, hyp.b_sa + 0.5 * a_ss
        )
        # with no live features the draw is pure heavy-tailed prior and can
        # wander into a region where births are impossible — hold it instead
        sigma_a = jnp.where(k_plus > 0, jnp.sqrt(sa2), gs.sigma_a)
    else:
        sigma_x, sigma_a = gs.sigma_x, gs.sigma_a
    if hyp.resample_alpha:
        HN = ibm.harmonic(int(N_global))
        alpha = ibm.gamma_draw(k_al, hyp.a_alpha + k_plus, hyp.b_alpha + HN)
    else:
        alpha = gs.alpha
    p_prime = jax.random.randint(k_pp, (), 0, P_)
    return sigma_x, sigma_a, alpha, p_prime


# --------------------------------------------------------------------------
# driver 1: vmap-simulated shards (single device; benchmarks/tests)
# --------------------------------------------------------------------------


def _hybrid_iteration_body(
    X_shards: Array,            # (P, N_p, D)
    gs: HybridGlobal,
    ss: HybridShard,
    hyp,
    L: int,
    N_g: float,
    backend: str,
    row_scan: Callable,
) -> tuple[HybridGlobal, HybridShard]:
    """One full hybrid iteration for ONE chain (vmap-simulated shards).

    Kept free of jit/static plumbing so every layout can reuse it:
    ``_build_vmap_fns`` jits it directly or vmaps it over a chain axis,
    and the chains-mesh x data-vmap layout runs it per chain device
    (``build_hybrid_fns``).
    """
    Z, Z_tail, tail_active, n_sat, n_ref = _vmap_sub_iterations(
        X_shards, ss.Z, ss.Z_tail, ss.tail_active, gs, N_g, L, backend,
        row_scan,
    )
    return _vmap_sync(X_shards, gs, Z, Z_tail, tail_active, n_sat, n_ref,
                      hyp, N_g)


def _vmap_sync(
    X_shards: Array,
    gs: HybridGlobal,
    Z: Array,
    Z_tail: Array,
    tail_active: Array,
    n_sat: Array,
    n_ref: Array,
    hyp,
    N_g: float,
) -> tuple[HybridGlobal, HybridShard]:
    """The master sync of the data-vmap layouts (simulated psum = sum
    over the shard axis), after the sub-iterations; clears the tails."""
    P_, N_p, D = X_shards.shape
    with jax.named_scope("ibp_sync"):
        tail_g = jnp.sum(tail_active, axis=0)  # only p' is nonzero
        Z, active_new, n_drop = jax.vmap(
            promote_tail, in_axes=(0, 0, None, None)
        )(Z, Z_tail, tail_g, gs.active)
        active_new = active_new[0]  # identical across shards
        n_drop = n_drop[0]

        stats = jax.vmap(local_stats)(X_shards, Z)
        stats = jax.tree.map(lambda x: jnp.sum(x, axis=0), stats)
        A, pi, active, m = master_step1(stats, active_new, gs, N_g, D)
        Z = Z * active[None, None, :]

        sse = jnp.sum(jax.vmap(local_sse, in_axes=(0, 0, None, None))(
            X_shards, Z, A, active
        ))
        sigma_x, sigma_a, alpha, p_prime = master_step2(
            sse, A, active, gs, hyp, N_g, D, P_
        )

        gs_new = HybridGlobal(
            A=A, pi=pi, active=active, alpha=alpha,
            sigma_x=sigma_x, sigma_a=sigma_a,
            key=jax.random.fold_in(gs.key, 7),
            p_prime=p_prime, it=gs.it + 1,
            overflow=gs.overflow + n_drop,
            tail_sat=gs.tail_sat + n_sat,
            tail_refresh=gs.tail_refresh + n_ref,
        )
        ss_new = HybridShard(
            Z=Z,
            Z_tail=jnp.zeros_like(Z_tail),
            tail_active=jnp.zeros_like(tail_active),
        )
    return gs_new, ss_new


# --------------------------------------------------------------------------
# multi-chain init: chain axis over every state leaf
# --------------------------------------------------------------------------


def init_multichain(
    key: Array,
    X_shards: Array,  # (P, N_p, D) — shared by every chain
    C: int,
    K_max: int,
    **kw,
) -> tuple[HybridGlobal, HybridShard]:
    """C independent chains: every state leaf gains a leading chain axis.

    Chains share the data but start from split PRNG keys, so their
    initial Z draws, feature seeds, and whole trajectories are
    independent — exactly what split-R-hat needs.
    """
    keys = jax.random.split(key, C)
    return jax.vmap(lambda k: init_hybrid(k, X_shards, K_max, **kw))(keys)


def _hybrid_stale_body(
    X_shards: Array,
    gs: HybridGlobal,
    ss: HybridShard,
    L: int,
    N_g: float,
    backend: str,
    row_scan: Callable,
) -> tuple[HybridGlobal, HybridShard]:
    """Bounded-staleness pass for ONE chain: shard sub-iterations WITHOUT
    the master sync (DESIGN.md §10).

    Shards keep Gibbs-sweeping Z (and p' keeps exploring its tail) against
    stale global parameters; tails carry over into the next full
    iteration's promotion. Non-exact by construction.

    The key consumed by the sweeps (fold 13) and the key handed to the
    next pass (fold 14) MUST differ — returning the consumed key would
    make the next iteration's sub-iterations replay the exact same
    per-(shard, l) uniform stream.
    """
    gs_sweep = dataclasses.replace(gs, key=jax.random.fold_in(gs.key, 13))
    Z, Z_tail, tail_active, _, _ = _vmap_sub_iterations(
        X_shards, ss.Z, ss.Z_tail, ss.tail_active, gs_sweep, N_g, L,
        backend, row_scan,
    )
    # stale passes don't touch gs — saturation and refreshes on them are
    # uncounted (the pass is explicitly non-exact; the counters stay
    # sync-boundary quantities)
    gs_out = dataclasses.replace(gs, key=jax.random.fold_in(gs.key, 14))
    return gs_out, HybridShard(Z=Z, Z_tail=Z_tail, tail_active=tail_active)


# --------------------------------------------------------------------------
# THE spec-driven construction path (DESIGN.md §13)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HybridFns:
    """Jitted iteration functions in a layout's NATIVE calling convention.

    * data="vmap" layouts (chains "none"/"vmap"):
        ``step(X_shards, gs, ss) -> (gs, ss)`` with HybridShard state
        (chain-batched leaves when chains="vmap").
    * mesh layouts (data="shardmap" and/or chains="mesh"):
        ``step(X_native, gs, Z, Z_tail, tail_active) -> (gs, Z, Zt, ta)``
        with device-resident mesh-layout buffers.

    ``stale`` is the bounded-staleness pass in the same convention.
    """

    step: Any
    stale: Any


def build_hybrid_fns(
    spec,
    hyp,
    *,
    N_global: int,
    mesh=None,
    data_axes: tuple[str, ...] = ("data",),
    chain_axes: tuple[str, ...] = ("chains",),
) -> HybridFns:
    """Build the hybrid iteration for ``spec``'s parallelism layout.

    This is hybrid.py's ONE construction entry point: every kernel knob
    (``L``, ``backend``, ``sync``) and the parallelism layout (``chains``
    x ``data``) are read off ``spec`` (a ``repro.core.ibp.api.SamplerSpec``
    or anything with those attributes); the tail's row scan is
    ``collapsed.tail_row_scan(spec.collapsed_backend, spec.chol_refresh)``,
    built here once. ``mesh`` is required for shard_map layouts;
    ``data_axes`` may name several mesh axes (flattened into the P
    processors — the production dry-run path), ``chain_axes`` exactly one.

    The same per-shard kernels back every layout, so the statistical
    algorithm is identical everywhere; only psum's realization changes
    (sum over a vmap axis vs. jax.lax.psum over mesh axes).
    """
    N_g = float(N_global)
    row_scan = tail_row_scan(spec.collapsed_backend, spec.chol_refresh)
    if spec.chains in ("none", "vmap") and spec.data == "vmap":
        return _build_vmap_fns(spec, hyp, N_g, row_scan)
    if mesh is None:
        raise ValueError(
            f"layout chains={spec.chains!r} x data={spec.data!r} needs a "
            f"mesh; pass mesh= (build_sampler constructs one from the spec)"
        )
    return _build_mesh_fns(spec, hyp, N_g, row_scan, mesh, data_axes,
                           chain_axes)


def _build_vmap_fns(spec, hyp, N_g: float, row_scan: Callable) -> HybridFns:
    """Single-device layouts: P shards simulated by vmap, optional chain
    axis vmapped OVER the full iteration (DESIGN.md §11)."""
    L, be = spec.L, spec.backend

    def step_one(Xs, gs, ss):
        return _hybrid_iteration_body(Xs, gs, ss, hyp, L, N_g, be, row_scan)

    def stale_one(Xs, gs, ss):
        return _hybrid_stale_body(Xs, gs, ss, L, N_g, be, row_scan)

    if spec.chains == "vmap":
        # built ONCE as jit(vmap(...)) — a bare vmap-of-jit would re-trace
        # the full iteration body on every call
        step = jax.vmap(step_one, in_axes=(None, 0, 0))
        stale = jax.vmap(stale_one, in_axes=(None, 0, 0))
    else:
        step, stale = step_one, stale_one
    return HybridFns(step=jax.jit(step), stale=jax.jit(stale))


def _build_mesh_fns(spec, hyp, N_g: float, row_scan: Callable, mesh,
                    data_axes: tuple[str, ...],
                    chain_axes: tuple[str, ...]) -> HybridFns:
    """shard_map layouts: data sharded over ``data_axes``
    (spec.data="shardmap") and/or chains sharded over ``chain_axes``
    (spec.chains="mesh"); composing both gives the 2-D chains x data mesh.

    ``spec.sync`` selects the master-sync schedule (DESIGN.md §8):

    * ``"staged"`` — three sequential all-reduces (tail mask -> promote ->
      (m, ZtZ, ZtX) -> draw A -> sse), a direct transliteration of the
      paper's "send summary statistics to the master" with the broadcast
      folded away by the replicated-master trick.
    * ``"fused"`` — ONE all-reduce. Exactness-preserving rewrites: (i) each
      shard computes its local stats with its OWN tail pre-scattered (zero
      columns everywhere except p', so the reduced stats equal the staged
      post-promotion stats); (ii) the residual SSE comes from the identity
      ||X - Z A||^2 = tr(X^T X) - 2<A, Z^T X> + <A, (Z^T Z) A>, evaluated
      from the already-reduced stats — no second reduction; (iii) the tail
      mask and tr(X^T X) ride in the same flattened payload. At the paper's
      statistics sizes (K <= 64) the sync is latency-bound, so collective
      COUNT, not bytes, is the cost — 3x fewer round trips.

    The stale pass runs with NO collectives at all — the whole point of
    bounded staleness on a real mesh is skipping the sync, so it never
    leaves the mesh layout or touches psum. Bitwise-equivalent to the
    vmap stale pass (same fold-13 sweep key, same fold-14 key advance).

    Chains are independent by construction: each chain block carries its
    own replicated master (gs leaves sharded over the chain axis), and no
    collective ever crosses ``chain_axes`` — the composed layout is C
    independent copies of the data-parallel algorithm.
    """
    import numpy as np

    L, be = spec.L, spec.backend
    sync = spec.sync
    chainful = spec.chains == "mesh"
    data_sharded = spec.data == "shardmap"
    if sync not in ("staged", "fused"):
        raise ValueError(f"sync={sync!r} not in ('staged', 'fused')")
    if chainful and len(chain_axes) != 1:
        raise ValueError(f"chains='mesh' needs exactly one chain axis, "
                         f"got {chain_axes}")
    P_ = (int(np.prod([mesh.shape[a] for a in data_axes]))
          if data_sharded else spec.P)
    d_ent = data_axes if len(data_axes) > 1 else data_axes[0]

    def make_fn(stale: bool):
        def call(X, gs: HybridGlobal, Z, Z_tail, tail_active):
            D = X.shape[-1]

            def finish(gs, A, pi, active, sse, n_drop, n_sat, n_ref, Zt_p,
                       ta_p):
                sigma_x, sigma_a, alpha, p_prime = master_step2(
                    sse, A, active, gs, hyp, N_g, D, P_
                )
                gs_new = HybridGlobal(
                    A=A, pi=pi, active=active, alpha=alpha,
                    sigma_x=sigma_x, sigma_a=sigma_a,
                    key=jax.random.fold_in(gs.key, 7),
                    p_prime=p_prime, it=gs.it + 1,
                    overflow=gs.overflow + n_drop,
                    tail_sat=gs.tail_sat + n_sat,
                    tail_refresh=gs.tail_refresh + n_ref,
                )
                return gs_new, jnp.zeros_like(Zt_p), jnp.zeros_like(ta_p)

            def block_stale(X_p, gs, Z_p, Zt_p, ta_p):
                ta = ta_p[0]
                idx = jax.lax.axis_index(data_axes)
                gs_sweep = dataclasses.replace(
                    gs, key=jax.random.fold_in(gs.key, 13)
                )
                Z_p, Zt_p, ta, _, _ = shard_sub_iterations(
                    X_p, Z_p, Zt_p, ta, gs_sweep, idx, N_g, L, be, row_scan,
                )
                gs_out = dataclasses.replace(
                    gs, key=jax.random.fold_in(gs.key, 14)
                )
                return gs_out, Z_p, Zt_p, ta[None, :]

            def block_staged(X_p, gs, Z_p, Zt_p, ta_p):
                ta = ta_p[0]  # (1, K_tail) local block -> (K_tail,)
                idx = jax.lax.axis_index(data_axes)
                Z_p, Zt_p2, ta, n_sat, n_ref = shard_sub_iterations(
                    X_p, Z_p, Zt_p, ta, gs, idx, N_g, L, be, row_scan
                )
                with jax.named_scope("ibp_sync"):
                    # AR 1: the other shards wait here for p′'s tail
                    with jax.named_scope("ar_tail"):
                        tail_g, n_sat_g, n_ref_g = jax.lax.psum(
                            (ta, n_sat, n_ref), data_axes)
                    Z_p, active_new, n_drop = promote_tail(Z_p, Zt_p2, tail_g,
                                                           gs.active)
                    stats = local_stats(X_p, Z_p)
                    with jax.named_scope("ar_stats"):
                        stats = jax.lax.psum(stats, data_axes)
                    A, pi, active, m = master_step1(stats, active_new, gs,
                                                    N_g, D)
                    Z_p = Z_p * active[None, :]
                    sse_p = local_sse(X_p, Z_p, A, active)
                    with jax.named_scope("ar_sse"):
                        sse = jax.lax.psum(sse_p, data_axes)
                    gs_new, Zt0, ta0 = finish(gs, A, pi, active, sse, n_drop,
                                              n_sat_g, n_ref_g, Zt_p, ta_p)
                return gs_new, Z_p, Zt0, ta0

            def block_fused(X_p, gs, Z_p, Zt_p, ta_p):
                ta = ta_p[0]
                idx = jax.lax.axis_index(data_axes)
                Z_p, Zt_p2, ta, n_sat, n_ref = shard_sub_iterations(
                    X_p, Z_p, Zt_p, ta, gs, idx, N_g, L, be, row_scan
                )
                with jax.named_scope("ibp_sync"):
                    K_max = Z_p.shape[1]
                    K_tail = ta.shape[0]
                    # local stats WITH own tail pre-scattered (non-p' adds
                    # zeros; p' uses the same deterministic slot assignment
                    # every shard re-derives after the reduce)
                    Z_stats, _, _ = promote_tail(Z_p, Zt_p2, ta, gs.active)
                    stats = local_stats(X_p, Z_stats)
                    # the saturation and refresh counts ride the single
                    # payload as float scalars (small exact integers —
                    # f32-exact)
                    payload = jnp.concatenate([
                        stats["ZtZ"].reshape(-1),
                        stats["ZtX"].reshape(-1),
                        stats["m"],
                        ta,
                        jnp.sum(X_p * X_p)[None],
                        n_sat.astype(X_p.dtype)[None],
                        n_ref.astype(X_p.dtype)[None],
                    ])
                    with jax.named_scope("ar_payload"):
                        g = jax.lax.psum(payload, data_axes)
                    o1 = K_max * K_max
                    o2 = o1 + K_max * X_p.shape[1]
                    ZtZ = g[:o1].reshape(K_max, K_max)
                    ZtX = g[o1:o2].reshape(K_max, X_p.shape[1])
                    m_g = g[o2:o2 + K_max]
                    tail_g = g[o2 + K_max:o2 + K_max + K_tail]
                    xx = g[-3]
                    n_sat_g = g[-2].astype(jnp.int32)
                    n_ref_g = g[-1].astype(jnp.int32)
                    Z_p, active_new, n_drop = promote_tail(Z_p, Zt_p2, tail_g,
                                                           gs.active)
                    A, pi, active, m = master_step1(
                        {"m": m_g, "ZtZ": ZtZ, "ZtX": ZtX}, active_new, gs,
                        N_g, D
                    )
                    Z_p = Z_p * active[None, :]
                    # SSE identity — exact, no second reduction
                    ZtXm = ZtX * active[:, None]
                    ZtZm = ZtZ * ibm.mask_outer(active)
                    sse = (xx - 2.0 * jnp.sum(A * ZtXm)
                           + jnp.sum(A * (ZtZm @ A)))
                    gs_new, Zt0, ta0 = finish(gs, A, pi, active, sse, n_drop,
                                              n_sat_g, n_ref_g, Zt_p, ta_p)
                return gs_new, Z_p, Zt0, ta0

            def block_vmap_data(X_full, gs, Z_c, Zt_c, ta_c):
                # data axis simulated by vmap INSIDE this chain's device:
                # one full single-chain iteration, no collectives
                ss_c = HybridShard(Z=Z_c, Z_tail=Zt_c, tail_active=ta_c)
                if stale:
                    gs2, ss2 = _hybrid_stale_body(X_full, gs, ss_c, L, N_g,
                                                  be, row_scan)
                else:
                    gs2, ss2 = _hybrid_iteration_body(X_full, gs, ss_c, hyp,
                                                      L, N_g, be, row_scan)
                return gs2, ss2.Z, ss2.Z_tail, ss2.tail_active

            if data_sharded:
                block = block_stale if stale else (
                    block_fused if sync == "fused" else block_staged)
            else:
                block = block_vmap_data

            if chainful:
                def shard_fn(X_b, gs_b, Z_b, Zt_b, ta_b):
                    # strip this chain's length-1 block axis, run the
                    # single-chain block, put the axis back
                    gs_c = jax.tree.map(lambda x: x[0], gs_b)
                    gs2, Z2, Zt2, ta2 = block(X_b, gs_c, Z_b[0], Zt_b[0],
                                              ta_b[0])
                    return (jax.tree.map(lambda x: x[None], gs2),
                            Z2[None], Zt2[None], ta2[None])
            else:
                shard_fn = block

            c_ent = chain_axes[0]
            if chainful and data_sharded:
                x_spec = P(d_ent)                 # replicated over chains
                g_leaf, z_spec = P(c_ent), P(c_ent, d_ent)
            elif chainful:
                x_spec = P()                      # full (P, N_p, D) copy
                g_leaf = z_spec = P(c_ent)
            else:
                x_spec, g_leaf, z_spec = P(d_ent), P(), P(d_ent)
            gspec = jax.tree.map(lambda _: g_leaf, gs)
            return jax.shard_map(
                shard_fn,
                mesh=mesh,
                in_specs=(x_spec, gspec, z_spec, z_spec, z_spec),
                out_specs=(gspec, z_spec, z_spec, z_spec),
                check_vma=False,
            )(X, gs, Z, Z_tail, tail_active)

        return jax.jit(call)

    return HybridFns(step=make_fn(stale=False), stale=make_fn(stale=True))
