"""Jitted wrapper for feature_stats: padding + backend select."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels._backend import default_interpret

from .kernel import DEFAULT_BLOCK_N, feature_stats_pallas

Array = jax.Array


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def feature_stats_core(
    X: Array, Z: Array, block_n: int = DEFAULT_BLOCK_N, interpret: bool = True
) -> tuple[Array, Array, Array]:
    N = X.shape[0]
    bn = min(block_n, max(8, N))
    pad = (-N) % bn
    if pad:  # zero rows contribute nothing to any of the three stats
        X = jnp.pad(X, ((0, pad), (0, 0)))
        Z = jnp.pad(Z, ((0, pad), (0, 0)))
    return feature_stats_pallas(X, Z, block_n=bn, interpret=interpret)


def feature_stats(
    X: Array, Z: Array, block_n: int = DEFAULT_BLOCK_N
) -> tuple[Array, Array, Array]:
    return feature_stats_core(X, Z, block_n=block_n,
                              interpret=default_interpret())
