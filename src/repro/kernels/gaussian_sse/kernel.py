"""Pallas TPU kernel: fused masked-residual SSE.

sigma_x's posterior needs ||X - Z A||^2 right after the master A draw. The
naive lowering materializes the (N_p, D) residual in HBM (write + re-read);
this kernel fuses (mask -> matmul -> subtract -> square -> reduce) per VMEM
block and accumulates into one (1, 1) f32 block across the grid. The
accumulation is a (1, 1) vector store: Mosaic cannot store a scalar to
VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_N = 512


def _kernel(x_ref, z_ref, a_ref, act_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xb = x_ref[...]                       # (BN, D)
    zb = z_ref[...] * act_ref[...]        # (BN, K) masked
    r = xb - jnp.dot(zb, a_ref[...], preferred_element_type=jnp.float32)
    out_ref[...] += jnp.sum(r * r, keepdims=True)


def gaussian_sse_pallas(
    X: jax.Array,
    Z: jax.Array,
    A: jax.Array,
    active: jax.Array,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jax.Array:
    N, D = X.shape
    K = Z.shape[1]
    assert N % block_n == 0, (N, block_n)
    grid = (N // block_n,)

    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, D), lambda i: (i, 0)),
            pl.BlockSpec((block_n, K), lambda i: (i, 0)),
            pl.BlockSpec((K, D), lambda i: (0, 0)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=interpret,
    )(
        X.astype(jnp.float32),
        Z.astype(jnp.float32),
        A.astype(jnp.float32),
        active.reshape(1, K).astype(jnp.float32),
    )
    return out[0, 0]
