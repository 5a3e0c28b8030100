"""Pure-jnp oracle for the collapsed_row kernel.

Semantics: the K-sequential collapsed Gibbs bit-flip recurrence for ONE
row n of Z (Griffiths & Ghahramani posterior-predictive form). Given the
row-deleted posterior map M = (Z_-^T Z_- + r I)^{-1} (masked to active
columns), H = M Z_-^T X_-, and the carried quadratic state
(v = M z, q = z^T M z, mean = z H), flip every bit k in order:

    x_n | z ~ N( z H,  sigma_x^2 (1 + z M z^T) I )

with prior odds m_k / (N - m_k). Each step is O(K + D): the flip moves
(v, q, mean) by (+-M[:, k], +-2 v_k + M_kk, +-H[k]) instead of re-solving.

This is the INNER LOOP of the collapsed sampler — the fast
``backend="fast"`` row step (core/ibp/collapsed.py) carries (L, M, H)
across rows with rank-one up/downdates and hands this recurrence the
same arguments the O(K^3) oracle computes from scratch, so ref and
kernel must agree bitwise given identical inputs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def collapsed_row_flip_ref(
    M: Array,         # (K, K) masked posterior map, symmetric
    H: Array,         # (K, D) posterior mean map
    x_n: Array,       # (D,) the row's observation (or residual)
    z: Array,         # (K,) current bits (row-deleted singletons cleared)
    v: Array,         # (K,) = M @ z
    q: Array,         # ()   = z @ v
    mean: Array,      # (D,) = z @ H
    u: Array,         # (K,) logit-uniform accept thresholds
    m_minus: Array,   # (K,) column counts with row n removed
    active_m: Array,  # (K,) live-column mask
    N: Array,         # ()   GLOBAL observation count (prior odds)
    inv2s2: Array,    # ()   = 1 / (2 sigma_x^2)
) -> tuple[Array, Array, Array, Array]:
    """Returns (z, v, q, mean) after one in-order pass over all K bits."""
    D = x_n.shape[0]
    K = z.shape[0]

    def bit_body(c, k):
        z, v, q, mean = c
        zk = z[k]
        Mk = M[:, k]
        Mkk = M[k, k]
        Hk = H[k]
        # state with bit k = 0
        v0 = v - zk * Mk
        q0 = q - zk * (2.0 * v[k] - Mkk)
        mean0 = mean - zk * Hk
        # state with bit k = 1
        v1 = v0 + Mk
        q1 = q0 + 2.0 * v0[k] + Mkk
        mean1 = mean0 + Hk
        s0 = 1.0 + q0
        s1 = 1.0 + q1
        r0 = x_n - mean0
        r1 = x_n - mean1
        rss0 = jnp.dot(r0, r0, precision="highest")
        rss1 = jnp.dot(r1, r1, precision="highest")
        ll0 = -0.5 * D * jnp.log(s0) - inv2s2 * rss0 / s0
        ll1 = -0.5 * D * jnp.log(s1) - inv2s2 * rss1 / s1
        mk = m_minus[k]
        logodds = jnp.log(jnp.maximum(mk, 1e-20)) - jnp.log(N - mk) + ll1 - ll0
        # sample; only live columns with support may flip
        may = (active_m[k] > 0) & (mk > 0.5)
        take1 = logodds > u[k]
        znk = jnp.where(may, take1.astype(z.dtype), z[k])
        pick1 = znk > 0.5
        v = jnp.where(pick1, v1, v0)
        q = jnp.where(pick1, q1, q0)
        mean = jnp.where(pick1, mean1, mean0)
        z = z.at[k].set(znk)
        return (z, v, q, mean), None

    (z, v, q, mean), _ = jax.lax.scan(bit_body, (z, v, q, mean), jnp.arange(K))
    return z, v, q, mean
