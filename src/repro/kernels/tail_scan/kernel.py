"""Pallas TPU kernel: the hybrid tail's whole collapsed row scan in one launch.

The collapsed tail on shard p′ is a serial scan over its rows, each row a
short chain of tiny dependent ops (K_tail 8, D 36). As an XLA
``while_loop`` every op of the body is a kernel launch of its own and
every ``lax.cond`` copies the carry in and out of its branches, so the
scan runs at the rate of its launches, not of its arithmetic. Here the
whole scan is one ``pallas_call`` (DESIGN.md §12, "The fused tail"):

* the carry — Lt = (chol W)ᵀ, M = W⁻¹, H = M ZᵀR, G = HHᵀ, the
  sufficient statistics (ZᵀZ, ZᵀR, m, active) and the counters — stays
  in VMEM (and SMEM for the integer counters) for the whole scan;
* a grid over row blocks streams R, the Z_tail rows and the row draws
  made ahead in XLA (logit-uniforms, MH proposal and acceptance
  uniform), and writes the updated Z_tail rows back block by block.
  The grid axis is ``"arbitrary"``, so the carry persists from block to
  block, and VMEM stays bounded whatever the number of rows;
* inside a block a ``fori_loop`` runs the rows. Branches are in-kernel
  ``lax.cond``s on scalars (probe, refresh, the rank-one moves) or
  selects (the cheap ones).

The row step is ``collapsed._packed_scan``'s at full width (B = K, so
no out-of-block slots and no overflow exit) with the carried-G float
path (``pack=True``) and the MH birth: remove the row (rank-one
downdate and its canary, Sherman–Morrison), drop singletons, probe the
drift every ``PROBE_EVERY`` rows, refactorize on cadence or on demand,
flip the K bits in order with the ``packed`` flavor's rss/rH algebra,
apply the MH birth with first-free-slot placement, and add the row
back (diagonal swaps, rank-one update).

Every product is float32 by VPU multiply-and-reduce, never an MXU pass:
at K 8 a one-vreg reduction is cheaper than a matmul, and no bf16
rounding can enter. The 8×8 Cholesky and its inverse are written out
column by column with masks (``jnp.linalg.cholesky`` does not lower in
Mosaic). Vectors live as (1, K) rows or (K, 1) columns;
``_Consts.col`` and ``_Consts.row`` turn one into the other exactly (a
one-hot reduction adds only zeros). Results agree with ``_packed_scan``
up to float rounding, so decisions agree up to likelihood-boundary
events.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_BLOCK = 256    # rows streamed per grid step
J_MAX = 4          # new-dish truncation (collapsed.J_MAX)
PROBE_EVERY = 4    # drift-probe cadence (collapsed.PROBE_EVERY)

f32 = jnp.float32
i32 = jnp.int32


class _Consts:
    """Index masks of one width K, built once per kernel trace."""

    def __init__(self, K: int):
        r = jax.lax.broadcasted_iota(i32, (K, K), 0)
        c = jax.lax.broadcasted_iota(i32, (K, K), 1)
        self.K = K
        self.eye = (r == c).astype(f32)
        self.le = (r <= c).astype(f32)       # [i, j] = i <= j
        self.ge = (c <= r).astype(f32)       # [j, i] = i <= j
        self.lane = jax.lax.broadcasted_iota(i32, (1, K), 1)
        self.sub = jax.lax.broadcasted_iota(i32, (K, 1), 0)

    def col(self, v):
        """(1, K) row -> (K, 1) column, exactly."""
        return jnp.sum(self.eye * v, axis=1, keepdims=True)

    def row(self, v):
        """(K, 1) column -> (1, K) row, exactly."""
        return jnp.sum(self.eye * v, axis=0, keepdims=True)

    def cumsum_col(self, v_row):
        """Inclusive prefix sum of a (1, K) row, as a (K, 1) column."""
        return jnp.sum(self.ge * v_row, axis=1, keepdims=True)

    def cumsum_row(self, v_col):
        """Inclusive prefix sum of a (K, 1) column, as a (1, K) row."""
        return jnp.sum(self.le * v_col, axis=0, keepdims=True)

    def onehot_row(self, k):
        return (self.lane == k).astype(f32)

    def onehot_col(self, k):
        return (self.sub == k).astype(f32)


def _s(x):
    """Sum of every element, kept as a (1, 1) array."""
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _max(x):
    return jnp.max(jnp.max(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _any(mask):
    return _max(jnp.where(mask, 1.0, 0.0)) > 0.5


def _all(mask):
    return ~_any(~mask)


def _flag(x):
    """A (1, 1) bool as a scalar, for ``lax.cond``."""
    return jnp.where(x, 1.0, 0.0)[0, 0] > 0.5


def _chol_rank1(c: _Consts, Lt, p_c, sigma: float, eps: float = 1e-12):
    """``math._chol_rank1_t``: chol(L Lᵀ + σ x xᵀ)ᵀ from Lt = Lᵀ and the
    column p = L⁻¹x (the row step reads the downdate's canary itself)."""
    p2 = p_c * p_c
    d = 1.0 + sigma * c.cumsum_col(c.row(p2))
    d_prev = d - sigma * p2
    d = jnp.maximum(d, eps)
    d_prev = jnp.maximum(d_prev, eps)
    r = jnp.sqrt(d / d_prev)
    qc = sigma * p_c / jnp.sqrt(d * d_prev)
    Gt = Lt * p_c
    # exclusive tail sums over rows: Ct[j] = sum_{i > j} p_i Lt[i]
    Ct = jnp.zeros_like(Lt)
    for i in range(1, c.K):
        Ct = Ct + (c.sub < i).astype(f32) * Gt[i:i + 1, :]
    return Lt * r + Ct * qc


def _g_rank1(c: _Consts, G, H, a_row, a_col, b):
    """``math.g_rank1``: G = HHᵀ through H' = H + a bᵀ (H pre-move)."""
    c_col = jnp.sum(H * b, axis=1, keepdims=True) + (0.5 * _s(b * b)) * a_col
    return G + (a_col * c.row(c_col) + c_col * a_row)


def _factor(c: _Consts, ZtZ, ZtX, act, ratio):
    """Exact (Lt, M, H, G) of the masked statistics: the refresh of
    ``_packed_scan`` (``padded_W`` → Cholesky → inverse → H, G)."""
    K = c.K
    act_c = c.col(act)
    keep2 = act_c * act
    W = ZtZ * keep2 + ratio * c.eye * keep2 + c.eye * (1.0 - act)
    # right-looking Cholesky, column by column (A stays exactly symmetric)
    A = W
    L = jnp.zeros_like(W)
    Lt = jnp.zeros_like(W)
    for j in range(K):
        a_row = A[j:j + 1, :]
        djj = _s(a_row * c.onehot_row(j))
        l_row = jnp.where(c.lane >= j, a_row / jnp.sqrt(djj), 0.0)
        l_col = c.col(l_row)
        L = L + l_col * c.onehot_row(j)
        Lt = Lt + c.onehot_col(j) * l_row
        A = A - l_col * l_row
    # Linv = L⁻¹ by forward substitution, row by row
    Linv = jnp.zeros_like(W)
    for i in range(K):
        li_row = L[i:i + 1, :]
        s = jnp.sum(c.col(li_row) * Linv, axis=0, keepdims=True)
        lii = _s(li_row * c.onehot_row(i))
        Linv = Linv + c.onehot_col(i) * ((c.onehot_row(i) - s) / lii)
    # M = Linvᵀ Linv, H = M (ZᵀX masked), G = H Hᵀ
    M = jnp.zeros_like(W)
    for k in range(K):
        rk = Linv[k:k + 1, :]
        M = M + c.col(rk) * rk
    M = M * keep2
    X = ZtX * act_c
    H = jnp.zeros_like(ZtX)
    for k in range(K):
        H = H + c.col(M[k:k + 1, :]) * X[k:k + 1, :]
    G = jnp.zeros_like(W)
    for j in range(K):
        G = G + jnp.sum(H * H[j:j + 1, :], axis=1,
                        keepdims=True) * c.onehot_row(j)
    return Lt, M, H, G


def _drift(c: _Consts, ZtZ, z_old, act_m, M1, H1, G1, ratio):
    """``_packed_scan``'s drift probe on the row-removed carry: the M
    residual ‖M W p − p‖∞ against the exact statistics, and the G
    residual ‖G p − H(Hᵀp)‖∞ / (1 + max|G|), with p = active_m."""
    am_c = c.col(act_m)
    tm = (jnp.sum(ZtZ * am_c, axis=0, keepdims=True)
          - z_old * _s(z_old * act_m))
    probe_t = act_m * tm + ratio * act_m
    d_m = _max(jnp.abs(jnp.sum(M1 * c.col(probe_t), axis=0, keepdims=True)
                       - act_m))
    sH = jnp.sum(H1 * am_c, axis=0, keepdims=True)
    d_g = _max(jnp.abs(jnp.sum(G1 * act_m, axis=1, keepdims=True)
                       - jnp.sum(H1 * sH, axis=1, keepdims=True)))
    d_g = d_g / (1.0 + _max(jnp.abs(G1)))
    return jnp.maximum(d_m, d_g)


def _flip(c: _Consts, M, H, G, x, z, v, q, mean, u, m_minus, N, inv2s2, D):
    """The ``packed`` flavor's K-sequential flips (rss/rH carry with the
    carried G), full width: inactive slots are exact no-ops. The state
    is held in columns, so each bit reads its scalars by static sublane
    slices and its M and G columns by static lane slices (M and G are
    exactly symmetric): no cross-lane reduction on the bit chain."""
    r = x - mean
    rss = _s(r * r)
    rH = jnp.sum(H * r, axis=1, keepdims=True)
    logprior = c.col(jnp.log(jnp.maximum(m_minus, 1e-20))
                     - jnp.log(N - m_minus))
    may = c.col(jnp.where(m_minus > 0.5, 1.0, 0.0))
    u, z, v = c.col(u), c.col(z), c.col(v)
    dM = jnp.sum(M * c.eye, axis=1, keepdims=True)
    dG = jnp.sum(G * c.eye, axis=1, keepdims=True)
    for k in range(c.K):
        at = slice(k, k + 1)
        zk, vk, rHk = z[at], v[at], rH[at]
        Mk, Gk = M[:, at], G[:, at]
        Mkk, Gkk = dM[at], dG[at]
        v0 = v - zk * Mk
        q0 = q - zk * (2.0 * vk - Mkk)
        rH0 = rH + zk * Gk
        rss0 = rss + zk * (2.0 * rHk + Gkk)
        v0k = vk - zk * Mkk
        rH0k = rHk + zk * Gkk
        v1 = v0 + Mk
        q1 = q0 + 2.0 * v0k + Mkk
        rss1 = rss0 - 2.0 * rH0k + Gkk
        s0 = 1.0 + q0
        s1 = 1.0 + q1
        ll0 = -0.5 * D * jnp.log(s0) - inv2s2 * rss0 / s0
        ll1 = -0.5 * D * jnp.log(s1) - inv2s2 * rss1 / s1
        logodds = logprior[at] + ll1 - ll0
        znk = jnp.where(may[at] > 0.5,
                        jnp.where(logodds > u[at], 1.0, 0.0), zk)
        pick1 = znk > 0.5
        v = jnp.where(pick1, v1, v0)
        q = jnp.where(pick1, q1, q0)
        rss = jnp.where(pick1, rss1, rss0)
        rH = jnp.where(pick1, rH0 - Gk, rH0)
        oh = c.onehot_col(k)
        z = z * (1.0 - oh) + znk * oh
    mean = jnp.sum(H * z, axis=0, keepdims=True)
    return c.row(z), c.row(v), q, mean


def _kernel(par_ref, r_ref, zt_ref, u_ref,
            zz_in, zx_in, m_in, act_in,
            zt_out, zz_ref, zx_ref, m_ref, act_ref, cnt_ref,
            lt_ref, mm_ref, h_ref, g_ref, since_ref,
            *, n_rows, row_block, N, refresh_every, drift_tol):
    K = zz_in.shape[0]
    D = zx_in.shape[1]
    c = _Consts(K)
    b = pl.program_id(0)
    ratio, inv2s2, rho, sqrt_ratio = (par_ref[0, 0], par_ref[0, 1],
                                      par_ref[0, 2], par_ref[0, 3])

    @pl.when(b == 0)
    def _():
        zz_ref[...] = zz_in[...]
        zx_ref[...] = zx_in[...]
        m_ref[...] = m_in[...]
        act_ref[...] = act_in[...]
        Lt, M, H, G = _factor(c, zz_in[...], zx_in[...], act_in[...], ratio)
        lt_ref[...] = Lt
        mm_ref[...] = M
        h_ref[...] = H
        g_ref[...] = G
        since_ref[0] = 0
        cnt_ref[0, 0] = 0
        cnt_ref[0, 1] = 0

    def row_step(i, carry):
        (Lt, M, H, G, ZtZ, ZtX, m, act, since, n_ref, n_sat) = carry
        x = r_ref[pl.ds(i, 1), :]
        z_old = zt_ref[pl.ds(i, 1), :]
        u = u_ref[pl.ds(i, 1), pl.ds(0, K)]
        j_prop = u_ref[pl.ds(i, 1), pl.ds(K, 1)]
        u_acc = u_ref[pl.ds(i, 1), pl.ds(K + 1, 1)]

        # ---- remove row n (rank-one downdate canary + Sherman–Morrison)
        m_minus = m - z_old
        zu = z_old * act
        zu_c = c.col(zu)
        w = jnp.sum(M * zu_c, axis=0, keepdims=True)        # (M zu)ᵀ
        w_c = c.col(w)
        p_c = jnp.sum(Lt * w, axis=1, keepdims=True)        # Lt w
        down_ok = _all(1.0 - c.cumsum_col(c.row(p_c * p_c)) > 1e-12)
        gamma = _s(zu * w)
        delta_s = jnp.maximum(1.0 - gamma, 1e-6)
        zH = jnp.sum(H * zu_c, axis=0, keepdims=True)
        wr = w / jnp.sqrt(delta_s)
        wd = w / delta_s
        wd_c = w_c / delta_s
        b_rm = zH - x
        M1 = M + (w_c / jnp.sqrt(delta_s)) * wr
        H1 = H + wd_c * b_rm
        G1 = _g_rank1(c, G, H, wd, wd_c, b_rm)
        drop = act * jnp.where(m_minus <= 0.5, 1.0, 0.0)
        z = z_old * (1.0 - drop)
        act_m = act * (1.0 - drop)
        has_drop = _any(drop > 0.5)
        dropped = _flag(has_drop)
        am_c = c.col(act_m)
        keep2 = am_c * act_m
        M1 = M1 * keep2
        H1 = H1 * am_c
        G1 = G1 * keep2

        # ---- drift monitor, every PROBE_EVERY rows
        def probe(_):
            return _drift(c, ZtZ, z_old, act_m, M1, H1, G1, ratio)

        drift = jax.lax.cond(since % PROBE_EVERY == 0, probe,
                             lambda _: jnp.zeros((1, 1), f32), None)
        need_v = ~down_ok | ~(drift <= drift_tol)
        need = (since >= refresh_every - 1) | _flag(need_v)

        def refresh(_):
            ZtZ_m = ZtZ - c.col(z_old) * z_old
            ZtX_m = ZtX - c.col(z_old) * x
            return _factor(c, ZtZ_m, ZtX_m, act_m, ratio)

        Lt_rm, M1, H1, G1 = jax.lax.cond(
            need, refresh, lambda _: (Lt, M1, H1, G1), None)
        since = jnp.where(need, 0, since + 1)
        n_ref = n_ref + need.astype(i32)

        # ---- (v, q, mean): closed form unless the map was rebuilt
        def vqm_matvec(_):
            v = jnp.sum(M1 * c.col(z), axis=0, keepdims=True)
            return v, _s(z * v), jnp.sum(H1 * c.col(z), axis=0,
                                         keepdims=True)

        def vqm_closed(_):
            gd = gamma / delta_s
            return wd, gd, zH + gd * (zH - x)

        v, q, mean = jax.lax.cond(need | dropped, vqm_matvec,
                                  vqm_closed, None)
        z, v, q, mean = _flip(c, M1, H1, G1, x, z, v, q, mean, u, m_minus,
                              N, inv2s2, D)

        # ---- MH birth: propose j ~ Poisson(α/N) (drawn ahead), accept
        # w.p. lik(j)/lik(0); first-free-slot placement
        s = 1.0 + q
        r = x - mean
        rss = _s(r * r)

        def ll(j):
            s_j = s + j * rho
            return -0.5 * D * jnp.log(s_j) - inv2s2 * rss / s_j

        free = 1.0 - jnp.maximum(act_m, z)
        n_free = _s(free)
        ok = j_prop <= jnp.minimum(float(J_MAX), n_free)
        dll = ll(jnp.clip(j_prop, 0.0, float(J_MAX))) - ll(0.0)
        acc = jnp.log(u_acc) < dll
        j_new = jnp.where(ok & acc, j_prop, 0.0)
        sat = acc & (j_prop <= float(J_MAX)) & (j_prop > n_free)
        free_rank = c.cumsum_row(c.col(free)) * free
        newbits = jnp.where((free_rank >= 1.0) & (free_rank <= j_new),
                            1.0, 0.0)
        z2 = z + newbits
        act_new = jnp.maximum(act_m, newbits)
        n_sat = n_sat + _flag(sat).astype(i32)

        # ---- add row n back
        m_new = m_minus * act_m + z2
        changed = need | _flag(_any((z2 != z_old) | (act_new != act)))
        zo_c = c.col(z_old)
        z2_c = c.col(z2)
        ZtZ_n = jnp.where(has_drop,
                          (ZtZ - zo_c * z_old) * keep2 + z2_c * z2,
                          ZtZ + z2_c * z2 - zo_c * z_old)
        ZtX_n = jnp.where(has_drop,
                          (ZtX - zo_c * x) * am_c + z2_c * x,
                          ZtX + (z2_c - zo_c) * x)
        moved = changed | dropped
        ZtZ_n = jnp.where(moved, ZtZ_n, ZtZ)
        ZtX_n = jnp.where(moved, ZtX_n, ZtX)

        def apply_moves(_):
            Lt1 = jax.lax.cond(
                need, lambda _: Lt_rm,
                lambda _: _chol_rank1(c, Lt, p_c, -1.0), None)
            # diagonal swaps: a bitwise no-op without drops or births
            Lt1 = Lt1 * keep2 + c.eye * (1.0 - act_m)
            Lt1 = Lt1 + c.eye * (newbits * (sqrt_ratio - 1.0))
            M1b = M1 + c.eye * (newbits / ratio)
            nb_keep = 1.0 - newbits
            H1b = H1 * c.col(nb_keep)
            G1b = G1 * (c.col(nb_keep) * nb_keep)
            w2 = jnp.sum(M1b * z2_c, axis=0, keepdims=True)
            p2_c = jnp.sum(Lt1 * w2, axis=1, keepdims=True)
            Lt2 = _chol_rank1(c, Lt1, p2_c, 1.0)
            d2 = 1.0 + _s(z2 * w2)
            w2r = w2 / jnp.sqrt(d2)
            M2 = M1b - c.col(w2r) * w2r
            b_add = x - jnp.sum(H1b * z2_c, axis=0, keepdims=True)
            a = w2 / d2
            a_c = c.col(a)
            H2 = H1b + a_c * b_add
            G2 = _g_rank1(c, G1b, H1b, a, a_c, b_add)
            return Lt2, M2, H2, G2

        Lt, M, H, G = jax.lax.cond(changed, apply_moves,
                                   lambda _: (Lt, M, H, G), None)
        zt_out[pl.ds(i, 1), :] = z2
        return (Lt, M, H, G, ZtZ_n, ZtX_n, m_new, act_new, since, n_ref,
                n_sat)

    carry = (lt_ref[...], mm_ref[...], h_ref[...], g_ref[...],
             zz_ref[...], zx_ref[...], m_ref[...], act_ref[...],
             since_ref[0], cnt_ref[0, 0], cnt_ref[0, 1])
    n_here = jnp.minimum(row_block, n_rows - b * row_block)
    (Lt, M, H, G, ZtZ, ZtX, m, act, since, n_ref,
     n_sat) = jax.lax.fori_loop(0, n_here, row_step, carry)
    lt_ref[...] = Lt
    mm_ref[...] = M
    h_ref[...] = H
    g_ref[...] = G
    zz_ref[...] = ZtZ
    zx_ref[...] = ZtX
    m_ref[...] = m
    act_ref[...] = act
    since_ref[0] = since
    cnt_ref[0, 0] = n_ref
    cnt_ref[0, 1] = n_sat


@functools.partial(jax.jit, static_argnames=(
    "N", "refresh_every", "drift_tol", "interpret"))
def tail_scan_pallas(Z, active, ZtZ, ZtX, m, R, u, j_prop, u_acc, sx, sa,
                     *, N: float, refresh_every: int, drift_tol: float,
                     interpret: bool = False):
    """Run the tail's row scan over every row of ``R`` in one kernel.

    ``Z`` (n, K) tail bits, ``active`` (K,), ``ZtZ`` (K, K), ``ZtX``
    (K, D), ``m`` (K,) its statistics, ``R`` (n, D) the residual; ``u``
    (n, K) the rows' logit-uniforms, ``j_prop``/``u_acc`` (n,) the MH
    birth's draws, as ``collapsed._packed_scan`` derives them. Returns
    (Z, active, ZtZ, ZtX, m, n_refresh, n_sat), as ``collapsed_row_scan``.
    """
    n, K = Z.shape
    D = R.shape[1]
    tb = min(ROW_BLOCK, n)
    par = jnp.stack([(sx / sa) ** 2, 0.5 / (sx ** 2), (sa / sx) ** 2,
                     jnp.sqrt((sx / sa) ** 2)]).astype(f32).reshape(1, 4)
    # one streamed array per row: the K logit-uniforms, then the MH draws
    draws = jnp.concatenate([u, j_prop[:, None], u_acc[:, None]],
                            axis=1).astype(f32)
    rows = lambda w: pl.BlockSpec((tb, w), lambda b: (b, 0))
    whole = lambda s: pl.BlockSpec(s, lambda b: (0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(
        _kernel, n_rows=n, row_block=tb, N=float(N),
        refresh_every=int(refresh_every), drift_tol=float(drift_tol))
    zt, zz, zx, m_o, act_o, cnt = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, tb),),
        in_specs=[smem, rows(D), rows(K), rows(K + 2),
                  whole((K, K)), whole((K, D)), whole((1, K)),
                  whole((1, K))],
        out_specs=[rows(K), whole((K, K)), whole((K, D)), whole((1, K)),
                   whole((1, K)), smem],
        out_shape=[jax.ShapeDtypeStruct((n, K), f32),
                   jax.ShapeDtypeStruct((K, K), f32),
                   jax.ShapeDtypeStruct((K, D), f32),
                   jax.ShapeDtypeStruct((1, K), f32),
                   jax.ShapeDtypeStruct((1, K), f32),
                   jax.ShapeDtypeStruct((1, 2), i32)],
        scratch_shapes=[pltpu.VMEM((K, K), f32), pltpu.VMEM((K, K), f32),
                        pltpu.VMEM((K, D), f32), pltpu.VMEM((K, K), f32),
                        pltpu.SMEM((1,), i32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ibp_tail_scan",
    )(par, R.astype(f32), Z.astype(f32), draws,
      ZtZ.astype(f32), ZtX.astype(f32), m.reshape(1, K).astype(f32),
      active.reshape(1, K).astype(f32))
    return zt, act_o[0], zz, zx, m_o[0], cnt[0, 0], cnt[0, 1]
