"""Gibbs sweep primitives.

``uncollapsed_sweep`` is the hot loop of the paper's hybrid sampler: for every
row n (data-parallel) and every instantiated feature k (sequential — the
likelihood couples features through the residual), resample

    P(Z_nk = 1 | pi_k, A, X_n) ∝ pi_k · N(X_n | Z_n A, sigma_x^2 I).

Implementation: keep the residual R = X - Z A as the carried state and scan
over k with rank-1 updates — O(K · N · D) per sweep, fully vectorized over
rows. This is the jnp oracle; ``repro.kernels.gibbs_flip`` is the Pallas TPU
version with the residual pinned in VMEM (select with backend="pallas").
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import math as ibm

Array = jax.Array


def _logit(p: Array) -> Array:
    p = jnp.clip(p, 1e-6, 1.0 - 1e-6)
    return jnp.log(p) - jnp.log1p(-p)


def uncollapsed_sweep(
    X: Array,
    Z: Array,
    A: Array,
    pi: Array,
    active: Array,
    sigma_x: Array,
    key: Array,
    backend: str = "jnp",
) -> Array:
    """One full Gibbs sweep of Z | pi, A over active columns. Returns new Z.

    Both backends run under the ``ibp_sweep`` named scope, so every op of
    the sweep (the Pallas custom call included) carries it in its HLO
    ``op_name`` (DESIGN.md §16).
    """
    with jax.named_scope("ibp_sweep"):
        if backend == "pallas":
            from repro.kernels.gibbs_flip import ops as _gf_ops

            return _gf_ops.gibbs_flip(X, Z, A, pi, active, sigma_x, key)
        return _uncollapsed_sweep_jnp(X, Z, A, pi, active, sigma_x, key)


@partial(jax.jit, static_argnames=())
def _uncollapsed_sweep_jnp(
    X: Array,
    Z: Array,
    A: Array,
    pi: Array,
    active: Array,
    sigma_x: Array,
    key: Array,
) -> Array:
    N, K = Z.shape
    R = X - ibm.dot(Z, A)              # residual under current Z
    anorm2 = jnp.sum(A * A, axis=1)    # (K,)
    lpi = _logit(pi)
    # pre-drawn uniforms, in logit space so the accept test is logit > u
    u = _logit(jax.random.uniform(key, (N, K), dtype=X.dtype))
    inv2s2 = 0.5 / (sigma_x**2)

    def body(carry, k):
        R, Z = carry
        a_k = A[k]
        z_k = Z[:, k]
        # residual with Z_nk = 0
        R0 = R + z_k[:, None] * a_k[None, :]
        # loglik(z=1) - loglik(z=0) = (2 R0·a_k - |a_k|^2) / (2 sigma^2)
        dll = (2.0 * ibm.dot(R0, a_k) - anorm2[k]) * inv2s2
        logits = lpi[k] + dll
        znew = jnp.where(active[k] > 0, (logits > u[:, k]).astype(Z.dtype), z_k)
        R = R0 - znew[:, None] * a_k[None, :]
        Z = Z.at[:, k].set(znew)
        return (R, Z), None

    (R, Z), _ = jax.lax.scan(body, (R, Z), jnp.arange(K))
    return Z


def sufficient_stats(X: Array, Z: Array) -> tuple[Array, Array, Array, Array]:
    """(m, ZtZ, ZtX, trXtX) for this shard — what the master sync reduces."""
    m = jnp.sum(Z, axis=0)
    ZtZ = Z.T @ Z
    ZtX = Z.T @ X
    trXtX = jnp.sum(X * X)
    return m, ZtZ, ZtX, trXtX
