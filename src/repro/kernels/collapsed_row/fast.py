"""CPU-fast flavor of the collapsed_row bit-flip recurrence.

Same posterior-predictive semantics as ``collapsed_row_flip_ref``, two
exactness-preserving rewrites (DESIGN.md §12):

* **O(K) per bit instead of O(K + D).** The likelihood only consumes the
  residual through its norm, so carry (rss = ‖x − zH‖², rH = H (x − zH))
  instead of the (D,)-dim mean: a flip moves them by (±2 rH_k + G_kk,
  ∓G[k]) with G = H Hᵀ. The occupancy-adaptive row step (DESIGN.md §14)
  CARRIES G across rows by the rank-two corrections matching each H move
  and passes it in — the strict O(K² + KD) row bound. When ``G`` is not
  supplied (legacy unpacked path, ``k_live_buckets="off"``), it is
  recomputed here per row as a single O(K²D) GEMM — the historical
  constants-for-big-O trade (DESIGN.md §12). The mean is reconstructed
  once (z @ H) on exit.
* **Packed-active iteration.** Inactive columns are exact no-ops of the
  recurrence (z_k = 0, flips masked), so the loop visits only the packed
  indices of ``active_m``, in increasing order — identical decisions to
  the full-K scan, with the trip count K₊ instead of K_max. On CPU this
  is a dynamic-bound while_loop; on TPU lockstep SIMD makes packing
  pointless, which is why the Pallas kernel keeps the full-K form.
  Under occupancy-adaptive packing every input is already the K_live
  block (K here = the bucket size, not K_max); nothing changes — the
  recurrence is shape-generic and the block is ordered canonically.

The float arithmetic differs from the ref form (incremental rss vs
fresh residual dots), so decisions can differ from ref's at
measure-zero likelihood-boundary events — the backend equivalence test
(tests/test_collapsed_fast.py) quantifies exactly this.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def collapsed_row_flip_fast(
    M: Array,         # (K, K) masked posterior map, symmetric
    H: Array,         # (K, D) posterior mean map
    x_n: Array,       # (D,)
    z: Array,         # (K,)
    v: Array,         # (K,) = M @ z
    q: Array,         # ()   = z @ v
    mean: Array,      # (D,) = z @ H
    u: Array,         # (K,) logit-uniform accept thresholds
    m_minus: Array,   # (K,)
    active_m: Array,  # (K,)
    N: Array,         # ()
    inv2s2: Array,    # ()
    G: Array | None = None,  # (K, K) = H Hᵀ, carried by the caller
) -> tuple[Array, Array, Array, Array]:
    """Returns (z, v, q, mean) — see collapsed_row_flip_ref for semantics."""
    K = z.shape[0]
    D = x_n.shape[0]
    if G is None:
        G = jnp.dot(H, H.T, precision="highest")
    r = x_n - mean
    rss = jnp.dot(r, r, precision="highest")
    rH = jnp.dot(H, r, precision="highest")
    logprior = jnp.log(jnp.maximum(m_minus, 1e-20)) - jnp.log(N - m_minus)
    ks = jnp.nonzero(active_m > 0.5, size=K, fill_value=0)[0]
    n_act = jnp.sum(active_m > 0.5).astype(jnp.int32)

    def body(c):
        i, z, v, q, rss, rH = c
        k = ks[i]
        zk = z[k]
        Mk = M[k]       # == M[:, k] (M symmetric)
        Mkk = Mk[k]
        Gk = G[k]
        Gkk = Gk[k]
        # state with bit k = 0
        v0 = v - zk * Mk
        q0 = q - zk * (2.0 * v[k] - Mkk)
        rH0 = rH + zk * Gk
        rss0 = rss + zk * (2.0 * rH[k] + Gkk)
        # state with bit k = 1
        v1 = v0 + Mk
        q1 = q0 + 2.0 * v0[k] + Mkk
        rss1 = rss0 - 2.0 * rH0[k] + Gkk
        s0 = 1.0 + q0
        s1 = 1.0 + q1
        ll0 = -0.5 * D * jnp.log(s0) - inv2s2 * rss0 / s0
        ll1 = -0.5 * D * jnp.log(s1) - inv2s2 * rss1 / s1
        logodds = logprior[k] + ll1 - ll0
        may = m_minus[k] > 0.5  # k is active by construction of ks
        znk = jnp.where(may, (logodds > u[k]).astype(z.dtype), zk)
        pick1 = znk > 0.5
        v = jnp.where(pick1, v1, v0)
        q = jnp.where(pick1, q1, q0)
        rss = jnp.where(pick1, rss1, rss0)
        rH = jnp.where(pick1, rH0 - Gk, rH0)
        return i + 1, z.at[k].set(znk), v, q, rss, rH

    c0 = (jnp.int32(0), z, v, q, rss, rH)
    _, z, v, q, rss, rH = jax.lax.while_loop(
        lambda c: c[0] < n_act, body, c0
    )
    return z, v, q, jnp.dot(z, H, precision="highest")
