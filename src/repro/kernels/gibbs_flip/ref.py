"""Pure-jnp oracle for the gibbs_flip kernel.

Semantics: one uncollapsed Gibbs sweep of Z | pi, A over all K columns
(sequential in k, vectorized over rows), with pre-drawn logit-uniforms.
Must match repro.core.ibp.sweeps._uncollapsed_sweep_jnp given the same
uniforms — the kernel and the sampler share this contract.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def gibbs_flip_ref(
    X: Array,        # (N, D)
    Z: Array,        # (N, K) in {0,1}
    A: Array,        # (K, D)
    logit_pi: Array, # (K,)
    active: Array,   # (K,) in {0,1}
    u_logit: Array,  # (N, K) logit-uniforms
    inv2s2: Array,   # () = 1 / (2 sigma_x^2)
) -> Array:
    R = X - jnp.dot(Z, A, precision="highest")
    anorm2 = jnp.sum(A * A, axis=1)

    def body(carry, k):
        R, Z = carry
        a_k = A[k]
        z_k = Z[:, k]
        R0 = R + z_k[:, None] * a_k[None, :]
        s = jnp.dot(R0, a_k, precision="highest")
        dll = (2.0 * s - anorm2[k]) * inv2s2
        logits = logit_pi[k] + dll
        znew = jnp.where(active[k] > 0, (logits > u_logit[:, k]).astype(Z.dtype), z_k)
        R = R0 - znew[:, None] * a_k[None, :]
        Z = Z.at[:, k].set(znew)
        return (R, Z), None

    (R, Z), _ = jax.lax.scan(body, (R, Z), jnp.arange(Z.shape[1]))
    return Z
