"""Pallas TPU kernel: the K-sequential collapsed-row bit-flip recurrence.

TPU adaptation (DESIGN.md §12): unlike ``gibbs_flip`` there is no row
blocking — the collapsed recurrence is sequential in k BY CONSTRUCTION
(each flip conditions on all previous flips through (v, q, mean)), and
it runs on one row at a time inside the row scan. The win is locality:
M (K, K), H (K, D) and the whole carry (z, v, q, mean) stay VMEM-resident
across all K steps, so the recurrence never touches HBM after the first
load — at K = 64, D = 1024 that is ~280 KB ≪ 16 MB VMEM.

All per-k selections use one-hot contractions instead of dynamic slicing
(lane-dim dynamic indexing is layout-hostile on TPU; one-hot matvecs hit
the MXU/VPU). M is passed TRANSPOSED so the one-hot row contraction
``onehot @ Mt`` yields column M[:, k] — bitwise the same values the jnp
oracle reads.

Occupancy-adaptive packing (DESIGN.md §14): when the caller runs the
packed row step, every operand here is already the K_live BLOCK — K
below is the bucket size, not K_max, so the sequential recurrence runs
K_live one-hot contractions instead of K_max and the VMEM-resident
(M, H) footprint shrinks quadratically/linearly with the bucket. The
kernel itself is shape-generic: the block is canonically ordered and
free in-block slots are exact no-ops (act = 0), so no packing logic
lives on this side.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(mt_ref, h_ref, x_ref, z_ref, v_ref, q_ref, mean_ref, u_ref,
            mm_ref, act_ref, n_ref, s_ref,
            zout_ref, vout_ref, qout_ref, meanout_ref):
    Mt = mt_ref[...]          # (K, K) = M^T; row k of Mt == M[:, k]
    H = h_ref[...]            # (K, D)
    x = x_ref[...]            # (1, D)
    z = z_ref[...]            # (1, K)
    v = v_ref[...]            # (1, K)
    q = q_ref[0, 0]           # scalar
    mean = mean_ref[...]      # (1, D)
    u = u_ref[...]            # (1, K)
    mm = mm_ref[...]          # (1, K)
    act = act_ref[...]        # (1, K)
    N = n_ref[0, 0]           # scalar
    inv2s2 = s_ref[0, 0]      # scalar

    K = z.shape[1]
    D = x.shape[1]
    kidx = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)

    def body(k, carry):
        z, v, q, mean = carry
        onehot = (kidx == k).astype(jnp.float32)              # (1, K)
        # exact selections: a TPU's default bf16 pass would round M, H
        Mk = jnp.dot(onehot, Mt, precision="highest",
                     preferred_element_type=jnp.float32)  # (1, K) = M[:, k]
        Hk = jnp.dot(onehot, H, precision="highest",
                     preferred_element_type=jnp.float32)  # (1, D)
        Mkk = jnp.sum(Mk * onehot)
        zk = jnp.sum(z * onehot)
        vk = jnp.sum(v * onehot)
        uk = jnp.sum(u * onehot)
        mk = jnp.sum(mm * onehot)
        act_k = jnp.sum(act * onehot)
        # state with bit k = 0
        v0 = v - zk * Mk
        q0 = q - zk * (2.0 * vk - Mkk)
        mean0 = mean - zk * Hk
        # state with bit k = 1
        v0k = jnp.sum(v0 * onehot)
        v1 = v0 + Mk
        q1 = q0 + 2.0 * v0k + Mkk
        mean1 = mean0 + Hk
        s0 = 1.0 + q0
        s1 = 1.0 + q1
        r0 = x - mean0
        r1 = x - mean1
        ll0 = -0.5 * D * jnp.log(s0) - inv2s2 * jnp.sum(r0 * r0) / s0
        ll1 = -0.5 * D * jnp.log(s1) - inv2s2 * jnp.sum(r1 * r1) / s1
        logodds = jnp.log(jnp.maximum(mk, 1e-20)) - jnp.log(N - mk) + ll1 - ll0
        may = (act_k > 0) & (mk > 0.5)
        take1 = (logodds > uk).astype(jnp.float32)
        znk = jnp.where(may, take1, zk)
        pick1 = znk > 0.5
        v = jnp.where(pick1, v1, v0)
        q = jnp.where(pick1, q1, q0)
        mean = jnp.where(pick1, mean1, mean0)
        z = z * (1.0 - onehot) + znk * onehot
        return z, v, q, mean

    z, v, q, mean = jax.lax.fori_loop(0, K, body, (z, v, q, mean))
    zout_ref[...] = z
    vout_ref[...] = v
    qout_ref[...] = jnp.reshape(q, (1, 1))  # Mosaic stores no VMEM scalar
    meanout_ref[...] = mean


def collapsed_row_flip_pallas(
    M: jax.Array,         # (K, K) symmetric masked posterior map
    H: jax.Array,         # (K, D)
    x_n: jax.Array,       # (D,)
    z: jax.Array,         # (K,)
    v: jax.Array,         # (K,)
    q: jax.Array,         # ()
    mean: jax.Array,      # (D,)
    u: jax.Array,         # (K,)
    m_minus: jax.Array,   # (K,)
    active_m: jax.Array,  # (K,)
    N: jax.Array,         # ()
    inv2s2: jax.Array,    # ()
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    K = z.shape[0]
    D = x_n.shape[0]
    f32 = jnp.float32
    full = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))

    zo, vo, qo, mo = pl.pallas_call(
        _kernel,
        grid=(1,),
        in_specs=[
            full((K, K)),   # M^T
            full((K, D)),   # H
            full((1, D)),   # x_n
            full((1, K)),   # z
            full((1, K)),   # v
            full((1, 1)),   # q
            full((1, D)),   # mean
            full((1, K)),   # u
            full((1, K)),   # m_minus
            full((1, K)),   # active_m
            full((1, 1)),   # N
            full((1, 1)),   # inv2s2
        ],
        out_specs=[
            full((1, K)), full((1, K)), full((1, 1)), full((1, D)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, K), f32),
            jax.ShapeDtypeStruct((1, K), f32),
            jax.ShapeDtypeStruct((1, 1), f32),
            jax.ShapeDtypeStruct((1, D), f32),
        ],
        interpret=interpret,
    )(
        M.T.astype(f32),
        H.astype(f32),
        x_n.reshape(1, D).astype(f32),
        z.reshape(1, K).astype(f32),
        v.reshape(1, K).astype(f32),
        jnp.asarray(q, f32).reshape(1, 1),
        mean.reshape(1, D).astype(f32),
        u.reshape(1, K).astype(f32),
        m_minus.reshape(1, K).astype(f32),
        active_m.reshape(1, K).astype(f32),
        jnp.asarray(N, f32).reshape(1, 1),
        jnp.asarray(inv2s2, f32).reshape(1, 1),
    )
    return zo[0], vo[0], qo[0, 0], mo[0]
