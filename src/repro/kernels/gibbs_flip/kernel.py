"""Pallas TPU kernel: blocked uncollapsed Gibbs sweep (the sampler hot loop).

TPU adaptation (DESIGN.md §4): rows are independent — tile them into VMEM
blocks of BLOCK_N; the (K, D) feature matrix A stays VMEM-resident across the
whole sequential k-loop, and the (BLOCK_N, D) residual is the loop carry, so
the K-step recurrence never touches HBM. Per k step the compute is two
(BLOCK_N, D) x (D,) MXU products — arithmetic intensity ~K× higher than the
naive form that re-reads X/Z/A from HBM every step.

All per-k selections use one-hot contractions instead of dynamic slicing —
lane-dim dynamic indexing is layout-hostile on TPU; one-hot matvecs hit the
MXU/VPU instead.

VMEM budget per block (f32): BLOCK_N·D (x, res) ·2 + BLOCK_N·K (z, u) ·2
+ K·D (A) + O(K). For BLOCK_N=256, D≤1024, K≤64: ~2.6 MB ≪ 16 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_N = 256


def _kernel(x_ref, z_ref, a_ref, lpi_ref, act_ref, anorm_ref, u_ref, s_ref,
            zout_ref):
    x = x_ref[...]            # (BN, D)
    z = z_ref[...]            # (BN, K)
    A = a_ref[...]            # (K, D)
    lpi = lpi_ref[...]        # (1, K)
    act = act_ref[...]        # (1, K)
    anorm = anorm_ref[...]    # (1, K)
    u = u_ref[...]            # (BN, K)
    inv2s2 = s_ref[0, 0]      # scalar

    K = z.shape[1]
    # f32 products: a TPU's default is one bf16 pass, which would round
    # the one-hot row selections below and the residual
    res = x - jnp.dot(z, A, precision="highest",
                      preferred_element_type=jnp.float32)
    kidx = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)

    def body(k, carry):
        res, z = carry
        onehot = (kidx == k).astype(jnp.float32)          # (1, K)
        a_k = jnp.dot(onehot, A, precision="highest",
                      preferred_element_type=jnp.float32)  # (1, D)
        z_k = jnp.sum(z * onehot, axis=1)                 # (BN,)
        u_k = jnp.sum(u * onehot, axis=1)                 # (BN,)
        anorm_k = jnp.sum(anorm * onehot)
        lpi_k = jnp.sum(lpi * onehot)
        act_k = jnp.sum(act * onehot)
        # residual with bit k cleared: dot against a_k
        s = jnp.sum(res * a_k, axis=1)                    # (BN,) = res @ a_k
        s0 = s + z_k * anorm_k
        logits = lpi_k + (2.0 * s0 - anorm_k) * inv2s2
        znew = jnp.where(act_k > 0, (logits > u_k).astype(z.dtype), z_k)
        delta = z_k - znew                                # (BN,)
        res = res + delta[:, None] * a_k
        z = z * (1.0 - onehot) + znew[:, None] * onehot
        return res, z

    res, z = jax.lax.fori_loop(0, K, body, (res, z))
    zout_ref[...] = z


def gibbs_flip_pallas(
    X: jax.Array,
    Z: jax.Array,
    A: jax.Array,
    logit_pi: jax.Array,
    active: jax.Array,
    u_logit: jax.Array,
    inv2s2: jax.Array,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jax.Array:
    """N must be a multiple of block_n (ops.py pads)."""
    N, D = X.shape
    K = Z.shape[1]
    assert N % block_n == 0, (N, block_n)
    grid = (N // block_n,)

    row_block = lambda shape: pl.BlockSpec(shape, lambda i: (i, 0))
    full = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))

    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            row_block((block_n, D)),   # X
            row_block((block_n, K)),   # Z
            full((K, D)),              # A
            full((1, K)),              # logit_pi
            full((1, K)),              # active
            full((1, K)),              # anorm2
            row_block((block_n, K)),   # u_logit
            full((1, 1)),              # inv2s2
        ],
        out_specs=row_block((block_n, K)),
        out_shape=jax.ShapeDtypeStruct((N, K), jnp.float32),
        interpret=interpret,
    )(
        X.astype(jnp.float32),
        Z.astype(jnp.float32),
        A.astype(jnp.float32),
        logit_pi.reshape(1, K).astype(jnp.float32),
        active.reshape(1, K).astype(jnp.float32),
        jnp.sum(A.astype(jnp.float32) ** 2, axis=1).reshape(1, K),
        u_logit.astype(jnp.float32),
        jnp.asarray(inv2s2, jnp.float32).reshape(1, 1),
    )
