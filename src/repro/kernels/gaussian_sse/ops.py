"""Jitted wrapper for gaussian_sse: padding + backend select."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels._backend import default_interpret

from .kernel import DEFAULT_BLOCK_N, gaussian_sse_pallas

Array = jax.Array


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def gaussian_sse_core(
    X: Array,
    Z: Array,
    A: Array,
    active: Array,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = True,
) -> Array:
    N = X.shape[0]
    bn = min(block_n, max(8, N))
    pad = (-N) % bn
    if pad:  # zero rows have zero residual: X=0, Z=0 -> r=0
        X = jnp.pad(X, ((0, pad), (0, 0)))
        Z = jnp.pad(Z, ((0, pad), (0, 0)))
    return gaussian_sse_pallas(X, Z, A, active, block_n=bn, interpret=interpret)


def gaussian_sse(
    X: Array, Z: Array, A: Array, active: Array, block_n: int = DEFAULT_BLOCK_N
) -> Array:
    return gaussian_sse_core(X, Z, A, active, block_n=block_n,
                             interpret=default_interpret())
