"""Collapsed Gibbs sampler for the linear-Gaussian IBP (Griffiths & Ghahramani).

This is the serial baseline the paper compares against (Fig. 1). A is fully
integrated out. For each row n we use the posterior-predictive form

    x_n | z_n, Z_-n, X_-n ~ N( z_n H_-,  sigma_x^2 (1 + z_n M_- z_n^T) I )

with M_- = (Z_-^T Z_- + (sx^2/sa^2) I)^{-1}, H_- = M_- Z_-^T X_-, which makes
each bit flip O(K + D) after the per-row posterior map is in hand.
New dishes use the exact truncated-Gibbs step: row-n singletons are dropped
and j_new ~ P(j | rest) ∝ Poisson(j; alpha/N) · lik(j) over j = 0..J_MAX
(lik(j) closed-form: new columns only add j·sa^2 to the predictive variance).

Everything is padded to K_max with an ``active`` mask.

Two row-step backends (DESIGN.md §12), selected by ``backend=``:

* ``"ref"``  — fresh O(K^3 + K^2 D) Cholesky factorization per row (the
  original sampler; kept as the exact oracle the fast path is tested
  against). Per sweep: O(N (K^3 + K^2 D)).
* ``"fast"`` — the factorization is CARRIED across the row scan and moved
  between rows by rank-one Cholesky up/downdates + Sherman–Morrison:
  remove-row = one downdate, singleton drop / new dish = diagonal
  identity swaps (the affected row/col of W is exactly ratio·e_k), add-row
  = one update; H moves by the matching rank-one corrections. O(K^2 + K D)
  algorithmic work per row. An exact refactorization every
  ``refresh_every`` rows plus a drift monitor (probe residual
  ‖M W p − p‖_∞ against the exactly maintained integer sufficient
  statistics, and the downdate's loss-of-positivity canary) force an
  early refresh when the carry degrades.

There is ONE implementation of the carried row step: ``_packed_scan``,
which runs the carry PACKED to a block of B columns (the unified core,
DESIGN.md §12). Under ``k_live_buckets="on"`` (default) B is the live
K⁺ bucket — a power-of-two B ∈ {8, 16, ..., K_max} holding every live
column plus the lowest-index free slots, canonically ordered — so every
dense op costs O(B²+BD) instead of O(K_max²+K_max·D), and G = HHᵀ joins
the carry (moved by the rank-two corrections matching each H move) to
keep the strict O(K²+KD) row bound. ``collapsed_sweep`` picks the
bucket host-side per sweep (and re-packs mid-sweep when a feature birth
overflows the block — the overflowing row is re-run at the bigger
bucket, so decisions stay on the oracle's trajectory). The serial
sweep's ``k_live_buckets="off"`` is the TOP-BUCKET degenerate point of
the same ladder: the identical packed core at B = K_max with the G
carry disabled (``carry_g=False``), which is bitwise-identical to the
pre-unification unpacked carry (the packed flip recomputes G = HHᵀ per
row, exactly as the legacy ``_row_step_fast`` did). The in-jit entry
``collapsed_row_scan`` runs the same core at the full padded width —
``pack=True`` carries G, ``pack=False`` keeps the legacy float path.
Packing is a pure permutation + refresh: decisions are ref-equivalent
within a tiny boundary budget in every mode.

The hybrid tail's row scan is chosen in one place, ``tail_row_scan``:
on a TPU the fast backend runs the whole scan as one Pallas kernel
(``fused_tail_scan``, ``kernels/tail_scan``); elsewhere, and for the
ref backend, it is ``collapsed_row_scan`` with the G carry.
``tail_path`` names which of the two runs.

The MH new-dish move additionally reports a TAIL-SATURATION counter
(``n_sat``): rows whose accepted birth proposal was rejected only for
lack of free columns. The hybrid sampler aggregates it into
``HybridGlobal.tail_sat``, where it drives adaptive ``K_tail`` growth
(runtime/driver.py) — the finite-truncation bias of the tail becomes a
monitored, convergent quantity instead of a silent cap.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels._backend import default_interpret, on_tpu
from repro.kernels.collapsed_row import collapsed_row_flip
from repro.kernels.tail_scan import tail_scan_pallas

from . import math as ibm
from .state import IBPHypers, IBPState

Array = jax.Array

J_MAX = 4  # truncation for per-row new-dish draws (P(j>4 | alpha/N) is negligible)

COLLAPSED_BACKENDS = ("ref", "fast")
DEFAULT_REFRESH = 64    # exact refactorization cadence of the fast path
DEFAULT_DRIFT_TOL = 1e-2  # probe-residual threshold forcing an early refresh
PROBE_EVERY = 4         # drift-probe cadence within the refresh window
K_LIVE_MODES = ("on", "off")  # occupancy-adaptive packing knob values
PACK_HEADROOM = J_MAX   # free in-block slots guaranteed at (re)pack time
U_CHUNK_ROWS = 512      # packed-scan uniform buffer rows held on device:
#                         the hoisted per-row uniforms are generated
#                         block-wise at this granularity instead of all
#                         (N, K_max) at once, so long serial scans
#                         (harvest runs) keep O(U_CHUNK_ROWS * K) memory


def _log_poisson(j: Array, lam: Array) -> Array:
    return j * jnp.log(lam) - lam - jax.lax.lgamma(j + 1.0)


def _mh_draws(kdish: Array, lam: Array, dtype) -> tuple[Array, Array]:
    """The MH birth's random numbers for one row, from its dish key
    alone: the proposal j ~ Poisson(lam) and the acceptance uniform."""
    kprop, kacc = jax.random.split(kdish)
    j_prop = jax.random.poisson(kprop, lam).astype(dtype)
    return j_prop, jax.random.uniform(kacc, (), dtype=dtype)


def _sample_dishes(kdish, q, mean, x_n, active_m, z, alpha, sx, sa, N, D,
                   birth, n_free_extra=0.0, mh=None):
    """Shared new-dish move: returns (z', active', newbits, j_new, sat).

    ``birth`` selects the move:
      * "gibbs" — exact truncated Gibbs over j ∈ 0..J_MAX (G&G; collapsed
        baseline).
      * "mh" — the paper's Metropolis-Hastings move for the hybrid tail:
        propose j ~ Poisson(alpha/N) and accept with the marginal-likelihood
        ratio (prior ∝ proposal, so they cancel). Out-of-capacity proposals
        are rejected.

    ``sat`` is the tail-saturation flag: True iff an MH proposal that the
    likelihood ACCEPTED was rejected purely for lack of free columns
    (j ≤ J_MAX but j > n_free) — i.e. the row wanted more in-flight
    births than the truncation admits. Always False for "gibbs" (the
    collapsed baseline's capacity is K_max; its truncation is tracked by
    the driver's overflow machinery, not here).

    ``n_free_extra`` is the packed row step's out-of-block free-slot
    count: the draw must see the CANONICAL free capacity (what the
    oracle sees), even when only the in-block slots are placeable — the
    caller detects non-placeable births via ``j_new`` vs ``newbits``.

    ``mh`` is the row's ``_mh_draws``, when the caller drew them ahead
    (the packed scan draws every row's at once); else they are drawn
    here from ``kdish``.
    """
    inv2s2 = 0.5 / (sx**2)
    lam = alpha / N
    s = 1.0 + q
    r = x_n - mean
    rss = ibm.dot(r, r)
    js = jnp.arange(J_MAX + 1, dtype=x_n.dtype)
    rho = (sa / sx) ** 2
    s_j = s + js * rho
    ll_j = -0.5 * D * jnp.log(s_j) - inv2s2 * rss / s_j
    free = 1.0 - jnp.maximum(active_m, z)
    n_free = jnp.sum(free) + n_free_extra
    if birth == "gibbs":
        # exact truncated Gibbs: j ~ ∝ Poisson(j; lam) lik(j)
        logits = _log_poisson(js, lam) + ll_j
        logits = jnp.where(js <= n_free, logits, -jnp.inf)
        j_new = jax.random.categorical(kdish, logits).astype(x_n.dtype)
        sat = jnp.zeros((), jnp.bool_)
    else:
        # paper's MH: propose j ~ Poisson(lam), accept w.p. lik(j)/lik(0)
        j_prop, u_acc = mh if mh is not None else _mh_draws(kdish, lam,
                                                            x_n.dtype)
        ok = (j_prop <= jnp.minimum(float(J_MAX), n_free))
        j_idx = jnp.clip(j_prop, 0, J_MAX).astype(jnp.int32)
        dll = ll_j[j_idx] - ll_j[0]
        acc = jnp.log(u_acc) < dll
        j_new = jnp.where(ok & acc, j_prop, 0.0)
        # capacity-bound rejection of an otherwise-accepted proposal: the
        # truncation (not the likelihood) vetoed these births
        sat = acc & (j_prop <= float(J_MAX)) & (j_prop > n_free)
    # place new dishes in the first j_new free slots
    free_rank = jnp.cumsum(free) * free  # 1-indexed rank among free slots
    newbits = ((free_rank >= 1.0) & (free_rank <= j_new)).astype(z.dtype)
    z = z + newbits
    active_new = jnp.maximum(active_m, newbits)
    return z, active_new, newbits, j_new, sat


def _row_step(carry, n, *, X, N, D, birth="gibbs"):
    """Resample row n's bits + new dishes, collapsed — the O(K^3) oracle.

    ``N`` is the GLOBAL number of observations — in the hybrid sampler the
    tail runs on processor p' with local rows but global-N priors
    ((m_k - Z_nk)/N and Poisson(alpha/N)), exactly as in the paper's
    pseudocode.

    The trailing ``n_sat`` carry element only accumulates the new-dish
    saturation flag — the sampling algebra and PRNG stream above it are
    the unchanged oracle.
    """
    Z, active, ZtZ, ZtX, m, alpha, sx, sa, key, n_sat = carry
    x_n = X[n]
    z = Z[n]
    # ---- remove row n from the sufficient statistics
    m_minus = m - z
    ZtZ_m = ZtZ - jnp.outer(z, z)
    ZtX_m = ZtX - jnp.outer(z, x_n)
    # drop row-n singletons (m_minus == 0 while z == 1): they are re-proposed
    # as part of the new-dish step (exact G&G scheme)
    singleton = active * (m_minus <= 0.5) * z
    z = z * (1.0 - singleton)
    active_m = active * (1.0 - (active * (m_minus <= 0.5)))  # live cols w/ support
    # ---- per-row factorization (exact; no carried state)
    ratio = (sx / sa) ** 2
    W = ibm.padded_W(ZtZ_m, active_m, ratio)
    M, _ = ibm.chol_inv_logdet(W)
    M = M * ibm.mask_outer(active_m)
    H = ibm.dot(M, ZtX_m * active_m[:, None])  # (K, D) posterior mean map
    v = ibm.dot(M, z)
    q = ibm.dot(z, v)
    mean = ibm.dot(z, H)
    inv2s2 = 0.5 / (sx**2)

    K = Z.shape[1]
    key, kbits, kdish, kslot = jax.random.split(key, 4)
    uu = jnp.clip(jax.random.uniform(kbits, (K,), dtype=X.dtype), 1e-7, 1.0 - 1e-7)
    u = jnp.log(uu) - jnp.log1p(-uu)  # logit(U): accept z=1 iff logodds > u

    z, v, q, mean = collapsed_row_flip(
        M, H, x_n, z, v, q, mean, u, m_minus, active_m, N, inv2s2,
        flavor="jnp",
    )

    # ---- new dishes, j = 0..J_MAX
    z, active_new, _, _, sat = _sample_dishes(
        kdish, q, mean, x_n, active_m, z, alpha, sx, sa, N, D, birth
    )

    # ---- add row n back
    m_new = m_minus * active_m + z  # dead/singleton cols contribute 0
    ZtZ_n = ZtZ_m * ibm.mask_outer(active_m) + jnp.outer(z, z)
    ZtX_n = ZtX_m * active_m[:, None] + jnp.outer(z, x_n)
    Z = Z.at[n].set(z)
    return (Z, active_new, ZtZ_n, ZtX_n, m_new, alpha, sx, sa, key,
            n_sat + sat.astype(n_sat.dtype)), None


def _exact_factor(ZtZ, ZtX, active, ratio):
    """O(K^3 + K^2 D) exact (Lt, M, H) from the sufficient statistics."""
    W = ibm.padded_W(ZtZ, active, ratio)
    L, M = ibm.chol_inv(W)
    M = M * ibm.mask_outer(active)
    H = ibm.dot(M, ZtX * active[:, None])
    return L.T, M, H


class _PackedCarry(NamedTuple):
    """Row-scan carry of the unified packed fast backend (DESIGN.md §12).
    Everything feature-indexed lives on the K_live block (size B,
    canonical columns ``cols`` ascending); only Z stays in the canonical
    layout (rows are gathered/scattered through ``cols`` per row).
    When ``carry_g`` is on, G = HHᵀ joins the carry — moved by the
    rank-two corrections matching each Sherman–Morrison H move instead
    of the per-row O(K²D) recompute in the packed flip; ``n``/``ovf``
    drive the early-exit while_loop (a birth that cannot be placed
    inside the block stops the scan BEFORE committing its row, so the
    host can repack and resume bitwise)."""

    n: Array          # () int32 — next row to process
    Z: Array          # (n_rows, K_canonical)
    active: Array     # (B,)
    ZtZ: Array        # (B, B)
    ZtX: Array        # (B, D)
    m: Array          # (B,)
    Lt: Array         # (B, B)
    M: Array          # (B, B)
    H: Array          # (B, D)
    G: Array          # (B, B) = H Hᵀ (carried; () placeholder when off)
    since: Array
    n_refresh: Array
    n_sat: Array      # () int32 — capacity-vetoed accepted births so far
    ovf: Array        # () bool — birth did not fit the packed block
    ubuf: Array       # (u_chunk, K_canonical) — current uniform block
    ubase: Array      # () int32 — first row-offset covered by ``ubuf``


def _key_chain(key: Array, n_rows: int) -> tuple[Array, Array, Array]:
    """The row scan's serial key chain: four-way split per row, as the
    oracle's row step does. Returns (chain_data (n_rows + 1, ...) — the
    carry key's data after j rows —, the bit-flip keys' data, the dish
    keys)."""
    def key_step(k, _):
        k2, kbits, kdish, _kslot = jax.random.split(k, 4)
        return k2, (jax.random.key_data(k2), jax.random.key_data(kbits),
                    kdish)

    _, (chain_next, kbits_data, kdish_all) = jax.lax.scan(
        key_step, key, None, length=n_rows)
    chain_data = jnp.concatenate(
        [jax.random.key_data(key)[None], chain_next])
    return chain_data, kbits_data, kdish_all


def _logit_uniforms(kbits_data: Array, base: Array, rows: int, K: int,
                    dtype) -> Array:
    """Logit-uniform flip thresholds for row offsets [base, base + rows)
    from the chain's bit-flip keys."""
    kb = jax.lax.dynamic_slice_in_dim(kbits_data, base, rows, 0)
    uu = jax.vmap(
        lambda kd: jax.random.uniform(
            jax.random.wrap_key_data(kd), (K,), dtype=dtype)
    )(kb)
    uu = jnp.clip(uu, 1e-7, 1.0 - 1e-7)
    return jnp.log(uu) - jnp.log1p(-uu)


@partial(jax.jit, static_argnames=("N", "birth", "B", "refresh_every",
                                   "drift_tol", "u_chunk_rows", "carry_g"))
def _packed_scan(
    Z, active, ZtZ, ZtX, m, X, key, alpha, sx, sa, start_row, *,
    N: float, birth: str, B: int, refresh_every: int,
    drift_tol: float = DEFAULT_DRIFT_TOL, u_chunk_rows: int = U_CHUNK_ROWS,
    carry_g: bool = True,
):
    """Packed row scan from ``start_row`` to the end of X — or to the
    first birth that does not fit the K_live block. THE single
    implementation of the carried collapsed row step (DESIGN.md §12).

    Inputs and outputs are CANONICAL (K_max-padded); the block gather at
    entry, the exact refactorization of the packed factor (+ G), and the
    scatter back at exit happen inside this one jitted function, so a
    bucket change costs exactly one repack + refresh. Returns
    (Z, active, ZtZ, ZtX, m, n_refresh, n_sat, key, ovf_row): ``ovf_row``
    is -1 when the scan completed, else the first UNPROCESSED row — all
    rows before it are committed, and the caller resumes from it after
    repacking (``ibm.pick_bucket`` guarantees the pending birth then
    fits, so every resume makes progress). ``n_sat`` counts committed
    rows whose accepted MH birth was vetoed by capacity (always 0 for
    ``birth="gibbs"``).

    ``carry_g=False`` is the TOP-BUCKET degenerate mode (B = K_max, the
    ``k_live_buckets="off"`` sweep): the G carry is skipped entirely and
    the packed flip recomputes G = HHᵀ per row, which reproduces the
    pre-unification unpacked carry BITWISE — the G carry is the only
    float-path difference between the two.

    Decision equivalence: the block holds every live column plus the
    lowest-index free slots in canonical order, the per-row uniform draw
    keeps the oracle's (K_canonical,) shape (gathered through ``cols``),
    and the new-dish draw sees the canonical free capacity — so the
    only packed-vs-oracle differences are float-rounding boundary
    events, in every mode.
    """
    n_rows, D = X.shape
    K_can = Z.shape[1]
    cols, min_out = ibm.block_select(active, B)
    n_out_free = float(K_can - B)  # out-of-block slots are free by invariant
    active_p = active[cols]
    ZtZ_p = ZtZ[cols][:, cols]
    ZtX_p = ZtX[cols]
    m_p = m[cols]
    ratio = (sx / sa) ** 2
    Lt0, M0, H0 = _exact_factor(ZtZ_p, ZtX_p, active_p, ratio)
    G0 = ibm.dot(H0, H0.T) if carry_g else jnp.zeros((), X.dtype)
    inv2s2 = 0.5 / (sx**2)

    # ---- hoist the oracle's per-row PRNG out of the serial loop: the
    # split chain is batched into one scan — bitwise the same stream,
    # but the K-wide generation no longer serializes with the row steps.
    # The chain is POSITIONAL in rows-processed-this-segment (the oracle
    # splits once per processed row, regardless of row index), so every
    # lookup below is relative to start_row; chain_data[j] = the carry
    # key after j processed rows, making the resume-after-overflow key
    # chain_data[ovf_row - start_row].
    #
    # The (K_canonical,)-wide uniform EXPANSION is chunked: only
    # ``u_chunk`` rows of logit-uniforms are resident at a time, refilled
    # inside the loop when the row index crosses the block (positional
    # key chain => block-wise generation is bitwise identical to the
    # all-rows hoist). The O(n_rows) buffers that remain — the key chain
    # and the per-row dish keys — are a few words per row, so very large
    # serial N no longer materializes an (N, K_max) buffer.
    #
    # ``chunked`` is a TRACE-TIME branch: when one block covers the scan
    # the in-loop refill cond is not traced at all. That matters beyond
    # tidiness — under a chain-vmapped caller lax.cond lowers to select
    # (both branches execute every iteration), which would turn the
    # amortized refill into a full block generation PER ROW. In-jit /
    # vmapped callers (the hybrid tail) therefore pass
    # u_chunk_rows >= n_rows (their K_canonical is the small K_tail, so
    # the full hoist is cheap); only the host-dispatched serial sweep —
    # never vmapped — takes the chunked path.
    sr = jnp.asarray(start_row, jnp.int32)
    u_chunk = min(u_chunk_rows, n_rows)
    chunked = u_chunk < n_rows
    j_cap = jnp.asarray(n_rows - u_chunk, jnp.int32)

    chain_data, kbits_data, kdish_all = _key_chain(key, n_rows)

    def gen_u(base):
        """Logit-uniform block for row offsets [base, base + u_chunk)."""
        return _logit_uniforms(kbits_data, base, u_chunk, K_can, X.dtype)

    # single-block case: the whole buffer is a loop-closure constant and
    # the carry's ubuf is an empty placeholder (cond-free hot loop)
    u_all = None if chunked else gen_u(jnp.zeros((), jnp.int32))

    # the MH birth's draws depend on the row's dish key alone (lam =
    # alpha/N is fixed for the scan): draw every row's at once, as
    # vectors, instead of one scalar Poisson loop pair per row step
    mh_all = (jax.vmap(lambda kd: _mh_draws(kd, alpha / N, X.dtype))(
        kdish_all) if birth == "mh" else None)

    def body(c: _PackedCarry) -> _PackedCarry:
        n = c.n
        active, ZtZ, ZtX, m = c.active, c.ZtZ, c.ZtX, c.m
        Lt, M, H, G = c.Lt, c.M, c.H, c.G
        x_n = X[n]
        z_old = c.Z[n][cols]
        # ---- remove row n (Sherman–Morrison; mirrors _row_step_fast on
        # the packed block — see that function for the algebra notes)
        m_minus = m - z_old
        zu = z_old * active
        w = ibm.dot(M, zu)
        p_down = ibm.dot(Lt, w)
        down_ok = jnp.all(1.0 - jnp.cumsum(p_down * p_down) > 1e-12)
        gamma = ibm.dot(zu, w)
        delta_s = jnp.maximum(1.0 - gamma, 1e-6)
        zH = ibm.dot(zu, H)
        wr = w / jnp.sqrt(delta_s)
        wd = w / delta_s
        b_rm = zH - x_n
        M1 = M + jnp.outer(wr, wr)
        H1 = H + jnp.outer(wd, b_rm)
        # pre-move H, same as the SM read
        G1 = ibm.g_rank1(G, H, wd, b_rm) if carry_g else G
        drop = active * (m_minus <= 0.5)
        z = z_old * (1.0 - drop)
        active_m = active * (1.0 - drop)
        has_drop = jnp.any(drop > 0.5)
        # unconditional drop masking: on the no-drop path the carry
        # already holds exact zeros on inactive rows/cols, so the
        # multiply is a bitwise no-op — cheaper than a branch at block
        # sizes (the unpacked path gates this; at B ≤ K_max the cond's
        # dispatch costs more than B² multiplies)
        keep2 = ibm.mask_outer(active_m)
        M1 = M1 * keep2
        H1 = H1 * active_m[:, None]
        if carry_g:
            G1 = G1 * keep2

        # ---- drift monitor: the M probe of the unpacked path, plus the
        # G-consistency residual ‖G p − H(Hᵀp)‖∞ (relative to max|G|) so
        # the carried G is covered by the same monitor (DESIGN.md §14)
        def do_probe(_):
            tm = ibm.dot(ZtZ, active_m) - z_old * ibm.dot(z_old, active_m)
            probe_t = active_m * tm + ratio * active_m
            d_m = jnp.max(jnp.abs(ibm.dot(M1, probe_t) - active_m))
            if not carry_g:
                return d_m
            d_g = jnp.max(jnp.abs(ibm.dot(G1, active_m)
                                  - ibm.dot(H1, ibm.dot(active_m, H1))))
            d_g = d_g / (1.0 + jnp.max(jnp.abs(G1)))
            return jnp.maximum(d_m, d_g)

        drift = jax.lax.cond(
            c.since % PROBE_EVERY == 0, do_probe,
            lambda _: jnp.zeros((), X.dtype), None,
        )
        need = ((c.since >= refresh_every - 1) | (~down_ok)
                | (~(drift <= drift_tol)))

        def do_refresh(_):
            ZtZ_m = ZtZ - jnp.outer(z_old, z_old)
            ZtX_m = ZtX - jnp.outer(z_old, x_n)
            L2, M2 = ibm.chol_inv(ibm.padded_W(ZtZ_m, active_m, ratio))
            M2 = M2 * ibm.mask_outer(active_m)
            H2 = ibm.dot(M2, ZtX_m * active_m[:, None])
            return L2.T, M2, H2, (ibm.dot(H2, H2.T) if carry_g else G)

        Lt_rm, M1, H1, G1 = jax.lax.cond(
            need, do_refresh, lambda _: (Lt, M1, H1, G1), None
        )
        since = jnp.where(need, 0, c.since + 1)
        n_refresh = c.n_refresh + need.astype(c.n_refresh.dtype)

        # ---- bit flips: the oracle's PRNG stream (canonical-width
        # uniforms, generated block-wise, gathered onto the block). The
        # refill is deterministic in the row offset, so an overflow
        # retry re-reads the identical draws even across the refill.
        j = n - sr
        if chunked:
            def refill(_):
                base = jnp.minimum((j // u_chunk) * u_chunk, j_cap)
                return gen_u(base), base

            ubuf, ubase = jax.lax.cond(
                j >= c.ubase + u_chunk, refill,
                lambda _: (c.ubuf, c.ubase), None,
            )
            u = ubuf[j - ubase][cols]
        else:
            ubuf, ubase = c.ubuf, c.ubase
            u = u_all[j][cols]
        kdish = kdish_all[j]

        def vqm_closed(_):
            gd = gamma / delta_s
            return wd, gd, zH + gd * (zH - x_n)

        def vqm_matvec(_):
            v = ibm.dot(M1, z)
            return v, ibm.dot(z, v), ibm.dot(z, H1)

        v, q, mean = jax.lax.cond(
            has_drop | need, vqm_matvec, vqm_closed, None
        )
        z, v, q, mean = collapsed_row_flip(
            M1, H1, x_n, z, v, q, mean, u, m_minus, active_m, N, inv2s2,
            flavor="packed", G=G1 if carry_g else None,
        )

        # ---- new dishes: canonical free capacity; placement must stay
        # inside the block AND below every out-of-block index to match
        # the oracle's first-free-slot rule — otherwise flag + bail
        z2, active_new, newbits, j_new, sat = _sample_dishes(
            kdish, q, mean, x_n, active_m, z, alpha, sx, sa, N, D, birth,
            n_free_extra=n_out_free,
            mh=None if mh_all is None else (mh_all[0][j], mh_all[1][j]),
        )
        top_col = jnp.max(jnp.where(newbits > 0.5, cols, -1))
        birth_ovf = (jnp.sum(newbits) < j_new) | (top_col >= min_out)

        # ---- add row n back (same gating as the unpacked fast path)
        m_new = m_minus * active_m + z2
        changed = (
            need | jnp.any(z2 != z_old) | jnp.any(active_new != active)
        )

        def stats_moved(_):
            def masked(_):
                return ((ZtZ - jnp.outer(z_old, z_old))
                        * ibm.mask_outer(active_m) + jnp.outer(z2, z2),
                        (ZtX - jnp.outer(z_old, x_n)) * active_m[:, None]
                        + jnp.outer(z2, x_n))

            def fused(_):
                return (ZtZ + jnp.outer(z2, z2) - jnp.outer(z_old, z_old),
                        ZtX + jnp.outer(z2 - z_old, x_n))

            return jax.lax.cond(has_drop, masked, fused, None)

        ZtZ_n, ZtX_n = jax.lax.cond(
            changed | has_drop, stats_moved, lambda _: (ZtZ, ZtX), None
        )

        def apply_moves(_):
            Lt1 = jax.lax.cond(
                need,
                lambda __: Lt_rm,
                lambda __: ibm.chol_rank1_downdate_t(Lt, p_down)[0],
                None,
            )

            def diag_swaps(ops):
                Lt1, M1, H1, G1 = ops
                keep2 = ibm.mask_outer(active_m)
                Lt1 = Lt1 * keep2 + jnp.diag(1.0 - active_m)
                Lt1 = Lt1 + jnp.diag(newbits * (jnp.sqrt(ratio) - 1.0))
                M1b = M1 + jnp.diag(newbits / ratio)
                H1b = H1 * (1.0 - newbits)[:, None]
                G1b = (G1 * ibm.mask_outer(1.0 - newbits) if carry_g
                       else G1)
                return Lt1, M1b, H1b, G1b

            Lt1, M1b, H1b, G1b = jax.lax.cond(
                has_drop | jnp.any(newbits > 0.5), diag_swaps,
                lambda ops: ops, (Lt1, M1, H1, G1),
            )
            w2 = ibm.dot(M1b, z2)
            Lt2 = ibm.chol_rank1_update_t(Lt1, ibm.dot(Lt1, w2))
            d2 = 1.0 + ibm.dot(z2, w2)
            w2r = w2 / jnp.sqrt(d2)
            M2 = M1b - jnp.outer(w2r, w2r)
            b_add = x_n - ibm.dot(z2, H1b)
            H2 = H1b + jnp.outer(w2 / d2, b_add)
            G2 = ibm.g_rank1(G1b, H1b, w2 / d2, b_add) if carry_g else G1b
            return Lt2, M2, H2, G2

        Lt_n, M_n, H_n, G_n = jax.lax.cond(
            changed, apply_moves, lambda _: (Lt, M, H, G), None
        )
        # on birth overflow: keep the pre-row carry verbatim (the key
        # chain is positional — the retry re-reads the identical draws).
        # Elementwise selects, NOT a lax.cond over the whole carry: a
        # branch returning every buffer (Z included) forces whole-buffer
        # copies per row, which dwarfs the packed savings.
        def sel(old, new_):
            return jnp.where(birth_ovf, old, new_)

        return _PackedCarry(
            n=n + (~birth_ovf).astype(jnp.int32),
            # overflow writes the just-gathered bits back: an in-place no-op
            Z=c.Z.at[n, cols].set(sel(z_old, z2)),
            active=sel(active, active_new),
            ZtZ=sel(ZtZ, ZtZ_n), ZtX=sel(ZtX, ZtX_n), m=sel(m, m_new),
            Lt=sel(Lt, Lt_n), M=sel(M, M_n), H=sel(H, H_n), G=sel(G, G_n),
            since=sel(c.since, since),
            n_refresh=sel(c.n_refresh, n_refresh),
            n_sat=sel(c.n_sat, c.n_sat + sat.astype(c.n_sat.dtype)),
            ovf=birth_ovf,
            # no sel(): the refill is positional in j, and an overflow
            # exits the loop — the host resumes with a fresh scan call
            ubuf=ubuf, ubase=ubase,
        )

    carry0 = _PackedCarry(
        n=jnp.asarray(start_row, jnp.int32), Z=Z, active=active_p,
        ZtZ=ZtZ_p, ZtX=ZtX_p, m=m_p, Lt=Lt0, M=M0, H=H0, G=G0,
        since=jnp.zeros((), jnp.int32), n_refresh=jnp.zeros((), jnp.int32),
        n_sat=jnp.zeros((), jnp.int32),
        ovf=jnp.zeros((), jnp.bool_),
        ubuf=(gen_u(jnp.zeros((), jnp.int32)) if chunked
              else jnp.zeros((0, K_can), X.dtype)),
        ubase=jnp.zeros((), jnp.int32),
    )
    out = jax.lax.while_loop(
        lambda c: (c.n < n_rows) & (~c.ovf), body, carry0
    )
    # scatter the block back to the canonical layout (out-of-block slots
    # are free: zero stats by the block invariant)
    dt = X.dtype
    active_c = jnp.zeros((K_can,), dt).at[cols].set(out.active)
    ZtZ_c = jnp.zeros((K_can, K_can), dt).at[cols[:, None],
                                             cols[None, :]].set(out.ZtZ)
    ZtX_c = jnp.zeros((K_can, D), dt).at[cols].set(out.ZtX)
    m_c = jnp.zeros((K_can,), dt).at[cols].set(out.m)
    ovf_row = jnp.where(out.ovf, out.n, -1)
    key_out = jax.random.wrap_key_data(chain_data[out.n - sr])
    return (out.Z, active_c, ZtZ_c, ZtX_c, m_c, out.n_refresh, out.n_sat,
            key_out, ovf_row)


def collapsed_row_scan(
    Z: Array,
    active: Array,
    ZtZ: Array,
    ZtX: Array,
    m: Array,
    X: Array,
    key: Array,
    alpha: Array,
    sx: Array,
    sa: Array,
    *,
    N: float,
    birth: str = "gibbs",
    backend: str = "ref",
    refresh_every: int = DEFAULT_REFRESH,
    drift_tol: float = DEFAULT_DRIFT_TOL,
    pack: bool = False,
    u_chunk_rows: int | None = None,
) -> tuple[Array, Array, Array, Array, Array, Array, Array]:
    """Scan the collapsed row step over every row of ``X``.

    The shared entry point of the serial baseline (``collapsed_sweep``)
    and the hybrid tail (``hybrid._tail_sub_iteration``). Returns
    (Z, active, ZtZ, ZtX, m, n_refresh, n_sat); ``n_refresh`` counts
    exact refactorizations (cadence + monitor, 0 on the ref backend)
    and ``n_sat`` the capacity-vetoed accepted MH births (the tail-
    saturation signal; 0 for ``birth="gibbs"``).

    The fast backend runs the ONE packed core at the full padded
    width (a static in-jit bucket: B = K; the bucketed B < K_max
    dispatch needs the host — ``collapsed_sweep``). ``pack`` selects the
    float path: ``True`` carries G = HHᵀ, removing the per-row O(K²D)
    GEMM from the packed flip (the hybrid tail's win); ``False`` keeps
    the legacy unpacked float path (G recomputed per row) — bitwise the
    pre-unification ``k_live_buckets="off"`` carry of the serial sweep.
    Ignored for ``backend="ref"``.

    ``u_chunk_rows=None`` keeps the historical defaults: the full
    (n_rows, K) uniform hoist for ``pack=True`` and the chunked
    U_CHUNK_ROWS buffer otherwise. The chunked refill is safe only for
    host-dispatched serial callers — in-jit / vmapped callers (the
    hybrid tail) MUST pass ``u_chunk_rows >= n_rows``: under vmap the
    chunk-refill lax.cond lowers to select and regenerates a whole
    block per row.
    """
    if backend not in COLLAPSED_BACKENDS:
        raise ValueError(f"backend={backend!r} not in {COLLAPSED_BACKENDS}")
    n_rows, D = X.shape
    if backend == "ref":
        body = partial(_row_step, X=X, N=N, D=D, birth=birth)
        carry = (Z, active, ZtZ, ZtX, m, alpha, sx, sa, key,
                 jnp.zeros((), jnp.int32))
        carry, _ = jax.lax.scan(body, carry, jnp.arange(n_rows))
        Z, active, ZtZ, ZtX, m = carry[:5]
        return Z, active, ZtZ, ZtX, m, jnp.zeros((), jnp.int32), carry[9]
    # full-width block: overflow is impossible (no out-of-block slots)
    Z, active, ZtZ, ZtX, m, n_refresh, n_sat, _, _ = _packed_scan(
        Z, active, ZtZ, ZtX, m, X, key, alpha, sx, sa, 0,
        N=N, birth=birth, B=Z.shape[1], refresh_every=refresh_every,
        drift_tol=drift_tol,
        u_chunk_rows=(u_chunk_rows if u_chunk_rows is not None
                      else n_rows if pack
                      else min(U_CHUNK_ROWS, n_rows)),
        carry_g=pack,
    )
    return Z, active, ZtZ, ZtX, m, n_refresh, n_sat


def tail_path(backend: str) -> str:
    """The path the hybrid tail's row scan takes for ``backend`` on this
    platform: ``"fused"`` — the whole scan as one Pallas kernel
    (``fused_tail_scan``) — on a TPU for the fast backend, else
    ``"scan"`` (``collapsed_row_scan``)."""
    return "fused" if on_tpu() and backend == "fast" else "scan"


def tail_row_scan(backend: str, refresh_every: int = DEFAULT_REFRESH):
    """The hybrid tail's row scan — the ONE place that chooses it
    (DESIGN.md §12). Returns ``scan(Z, active, ZtZ, ZtX, m, X, key,
    alpha, sx, sa, *, N)`` -> (Z, active, ZtZ, ZtX, m, n_refresh,
    n_sat), MH births throughout: the fused kernel where ``tail_path``
    says ``"fused"``, else ``collapsed_row_scan`` with the carried G
    (for ``"ref"``, the ``_row_step`` oracle)."""
    if backend not in COLLAPSED_BACKENDS:
        raise ValueError(f"backend={backend!r} not in {COLLAPSED_BACKENDS}")
    if tail_path(backend) == "fused":
        return partial(fused_tail_scan, refresh_every=refresh_every)
    # pack=True hoists every row's uniforms (u_chunk_rows = n_rows): under
    # chains="vmap" the tail is batched, and the chunked refill's cond
    # would lower to select and regenerate a whole block per row
    return partial(collapsed_row_scan, birth="mh", backend=backend,
                   refresh_every=refresh_every, pack=True)


def fused_tail_scan(
    Z: Array,
    active: Array,
    ZtZ: Array,
    ZtX: Array,
    m: Array,
    X: Array,
    key: Array,
    alpha: Array,
    sx: Array,
    sa: Array,
    *,
    N: float,
    refresh_every: int = DEFAULT_REFRESH,
    drift_tol: float = DEFAULT_DRIFT_TOL,
) -> tuple[Array, Array, Array, Array, Array, Array, Array]:
    """The hybrid tail's row scan as one kernel launch (DESIGN.md §12,
    "The fused tail"): ``collapsed_row_scan(birth="mh", backend="fast",
    pack=True)`` with the row loop in ``kernels/tail_scan``.

    The rows' random numbers are drawn here, in XLA, exactly as
    ``_packed_scan`` draws them — the key chain, the logit-uniforms and
    the MH draws — so both paths (and the benchmark's reference, which
    replays the chain) see the same numbers. Returns (Z, active, ZtZ,
    ZtX, m, n_refresh, n_sat) with ``collapsed_row_scan``'s meaning.
    """
    n_rows, _ = X.shape
    _, kbits_data, kdish_all = _key_chain(key, n_rows)
    u = _logit_uniforms(kbits_data, jnp.zeros((), jnp.int32), n_rows,
                        Z.shape[1], X.dtype)
    j_prop, u_acc = jax.vmap(lambda kd: _mh_draws(kd, alpha / N, X.dtype))(
        kdish_all)
    Zo, active, ZtZ, ZtX, m, n_refresh, n_sat = tail_scan_pallas(
        Z, active, ZtZ, ZtX, m, X, u, j_prop, u_acc, sx, sa,
        N=N, refresh_every=refresh_every, drift_tol=drift_tol,
        interpret=default_interpret(),
    )
    return Zo.astype(Z.dtype), active, ZtZ, ZtX, m, n_refresh, n_sat


def _finish_sweep(state, X, hyp, Z, active, ZtZ, ZtX, m,
                  key, kalpha, ksx, ksa) -> IBPState:
    """Post-scan pruning + hyperparameter updates shared by every sweep
    path (the jitted unpacked sweep traces it inline; the host-bucketed
    packed sweep calls the jitted wrapper below)."""
    N, D = X.shape
    alpha, sx, sa = state.alpha, state.sigma_x, state.sigma_a

    # prune columns that died during the sweep
    active = active * (m > 0.5)
    mask2 = ibm.mask_outer(active)
    ZtZ = ZtZ * mask2
    ZtX = ZtX * active[:, None]
    Z = Z * active[None, :]
    m = m * active
    k_plus = jnp.sum(active)

    # alpha | K+ ~ Gamma(a + K+, b + H_N)
    if hyp.resample_alpha:
        HN = ibm.harmonic(N)
        alpha = ibm.gamma_draw(kalpha, hyp.a_alpha + k_plus, hyp.b_alpha + HN)

    # sigma_x, sigma_a via random-walk MH on log-scale against collapsed lik
    if hyp.resample_sigmas:
        trXtX = jnp.sum(X * X)

        def cll(sx_, sa_):
            return ibm.collapsed_loglik(
                trXtX, ZtX, ZtZ, active, jnp.float32(N), D, sx_, sa_
            )

        def mh(key_, cur, other, which):
            kprop, kacc = jax.random.split(key_)
            prop = cur * jnp.exp(0.1 * jax.random.normal(kprop, (), dtype=cur.dtype))
            if which == "x":
                d = cll(prop, other) - cll(cur, other)
            else:
                d = cll(other, prop) - cll(other, cur)
            # log-normal RW: include log-scale Jacobian (log prop - log cur)
            d = d + jnp.log(prop) - jnp.log(cur)
            acc = jnp.log(jax.random.uniform(kacc, (), dtype=cur.dtype)) < d
            return jnp.where(acc, prop, cur)

        sx = mh(ksx, sx, sa, "x")
        sa = mh(ksa, sa, sx, "a")

    return IBPState(
        Z=Z, A=state.A, pi=state.pi, active=active, tail=state.tail,
        alpha=alpha, sigma_x=sx, sigma_a=sa, key=key,
        p_prime=state.p_prime, it=state.it + 1,
    )


_finish_sweep_jit = jax.jit(_finish_sweep, static_argnames=("hyp",))


@partial(jax.jit, static_argnames=("hyp", "backend", "refresh_every"))
def _collapsed_sweep_jit(
    state: IBPState,
    X: Array,
    hyp: IBPHypers,
    backend: str = "ref",
    refresh_every: int = DEFAULT_REFRESH,
) -> IBPState:
    """One fully-jitted collapsed sweep (ref, or the unified fast core
    at the TOP bucket: B = K_max, legacy no-G float path)."""
    N, D = X.shape
    Z, active = state.Z, state.active
    m, ZtZ, ZtX, _ = _sweep_stats(Z, active, X)
    key, ksweep, kalpha, ksx, ksa = jax.random.split(state.key, 5)

    Z, active, ZtZ, ZtX, m, _, _ = collapsed_row_scan(
        Z, active, ZtZ, ZtX, m, X, ksweep,
        state.alpha, state.sigma_x, state.sigma_a,
        N=float(N), birth="gibbs", backend=backend,
        refresh_every=refresh_every,
    )
    return _finish_sweep(state, X, hyp, Z, active, ZtZ, ZtX, m,
                         key, kalpha, ksx, ksa)


def _sweep_stats(Z, active, X):
    """Exact sweep-entry sufficient statistics (+ K⁺ for bucket choice)."""
    m = jnp.sum(Z * active[None, :], axis=0)
    ZtZ = ibm.dot(Z.T, Z) * ibm.mask_outer(active)
    ZtX = ibm.dot(Z.T, X) * active[:, None]
    return m, ZtZ, ZtX, jnp.sum(active)


@partial(jax.jit, static_argnames=("hyp", "refresh_every", "B"))
def _packed_sweep_jit(state, X, hyp, refresh_every, B):
    """One FUSED packed sweep attempt at bucket ``B``: stats + packed
    scan from row 0 + hyper-update finish, all in one dispatch.

    Returns (finished_state, raw_segment_outputs, ovf_row). On the
    common no-overflow sweep the host uses ``finished_state`` directly —
    one dispatch plus two scalar fetches (the pre-sweep occupancy for
    the bucket choice and ``ovf_row``), nearly the dispatch profile of
    the unpacked jitted sweep. On the rare birth overflow the finish is
    discarded and the host resumes segment-wise from
    ``raw_segment_outputs`` (the speculative finish is the only wasted
    work).
    """
    N, D = X.shape
    m, ZtZ, ZtX, _ = _sweep_stats(state.Z, state.active, X)
    key, ksweep, kalpha, ksx, ksa = jax.random.split(state.key, 5)
    Z, active, ZtZ2, ZtX2, m2, _, _, ksweep2, ovf_row = _packed_scan(
        state.Z, state.active, ZtZ, ZtX, m, X, ksweep,
        state.alpha, state.sigma_x, state.sigma_a, 0,
        N=float(N), birth="gibbs", B=B, refresh_every=refresh_every,
    )
    done = _finish_sweep(state, X, hyp, Z, active, ZtZ2, ZtX2, m2,
                         key, kalpha, ksx, ksa)
    raw = (Z, active, ZtZ2, ZtX2, m2, ksweep2, key, kalpha, ksx, ksa)
    return done, raw, ovf_row


def _collapsed_sweep_packed(
    state: IBPState,
    X: Array,
    hyp: IBPHypers,
    refresh_every: int,
    seg_log: list | None = None,
) -> IBPState:
    """Host-bucketed packed sweep (DESIGN.md §14).

    The host picks the K_live bucket — the smallest power-of-two bucket
    holding K⁺ + PACK_HEADROOM (``ibm.pick_bucket``) — and runs ONE
    fused jitted sweep at that static width (``_packed_sweep_jit``). A
    birth overflowing the block returns early with the finish discarded;
    the host then re-picks the bucket from the post-segment occupancy
    (repack UP when births filled the headroom; the shrink direction
    falls out for free at the next sweep boundary, whose segment start
    is an exact refactorization anyway) and resumes segment-wise from
    the first unprocessed row via ``_packed_scan``. The jit cache holds
    at most one entry per bucket — O(log K_max).

    ``seg_log`` (tests/benchmarks) receives one ``(bucket, start_row)``
    tuple per segment.
    """
    N, D = X.shape
    K_max = state.Z.shape[1]
    buckets = ibm.live_buckets(K_max)
    kp = int(jnp.sum(state.active))
    B = ibm.pick_bucket(buckets, kp, PACK_HEADROOM)
    if seg_log is not None:
        seg_log.append((B, 0))
    done, raw, ovf_row = _packed_sweep_jit(
        state, X, hyp=hyp, refresh_every=refresh_every, B=B)
    ovf = int(ovf_row)
    if ovf < 0:
        return done
    # rare path: mid-sweep birth overflow — resume segment-wise
    Z, active, ZtZ, ZtX, m, ksweep, key, kalpha, ksx, ksa = raw
    alpha, sx, sa = state.alpha, state.sigma_x, state.sigma_a
    row = ovf
    kp = int(jnp.sum(active))
    while row < N:
        B = ibm.pick_bucket(buckets, kp, PACK_HEADROOM)
        if seg_log is not None:
            seg_log.append((B, row))
        Z, active, ZtZ, ZtX, m, _, _, ksweep, ovf_row = _packed_scan(
            Z, active, ZtZ, ZtX, m, X, ksweep, alpha, sx, sa, row,
            N=float(N), birth="gibbs", B=B, refresh_every=refresh_every,
        )
        # ONE host round-trip per segment: the overflow row and the
        # next bucket choice's occupancy fetch together
        ovf, kp = map(int, jax.device_get((ovf_row, jnp.sum(active))))
        row = N if ovf < 0 else ovf
    return _finish_sweep_jit(state, X, hyp=hyp, Z=Z, active=active,
                             ZtZ=ZtZ, ZtX=ZtX, m=m, key=key,
                             kalpha=kalpha, ksx=ksx, ksa=ksa)


def collapsed_sweep(
    state: IBPState,
    X: Array,
    hyp: IBPHypers,
    backend: str = "ref",
    refresh_every: int = DEFAULT_REFRESH,
    k_live_buckets: str = "on",
    seg_log: list | None = None,
) -> IBPState:
    """One full collapsed Gibbs sweep over all rows + hyperparameter updates.

    ``k_live_buckets`` selects occupancy-adaptive packing for the
    fast backend (DESIGN.md §12): ``"on"`` (default) runs the
    unified packed core on the live K⁺ bucket via the host-dispatched
    packed scan; ``"off"`` runs the SAME core at the top bucket
    (B = K_max, G carry disabled) in one fully-jitted sweep — bitwise
    the pre-unification unpacked carry. The ref backend has no carry
    and ignores the knob.
    """
    if k_live_buckets not in K_LIVE_MODES:
        raise ValueError(
            f"k_live_buckets={k_live_buckets!r} not in {K_LIVE_MODES}"
        )
    if backend not in COLLAPSED_BACKENDS:
        raise ValueError(f"backend={backend!r} not in {COLLAPSED_BACKENDS}")
    if backend == "ref" or k_live_buckets == "off":
        return _collapsed_sweep_jit(state, X, hyp, backend=backend,
                                    refresh_every=refresh_every)
    return _collapsed_sweep_packed(state, X, hyp, refresh_every,
                                   seg_log=seg_log)
