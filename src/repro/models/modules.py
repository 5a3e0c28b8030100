"""Minimal pure-JAX module primitives (no flax): param init + apply fns.

Params are nested dicts of jax.Arrays. Every init fn returns (params, pspec)
where pspec mirrors the param tree with jax.sharding.PartitionSpec leaves —
sharding is declared next to the parameter it belongs to, so the launcher can
pjit any model without model-specific knowledge.

Axis-name conventions used in pspecs (resolved by parallel/mesh.py):
  "fsdp"   -> data(+pod) axes when FSDP is on, else None
  "tp"     -> the model/tensor axis
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Array = jax.Array

# logical axis placeholders; parallel/mesh.py maps them to mesh axes
FSDP = "__fsdp__"
TP = "__tp__"


def truncated_normal_init(key, shape, scale, dtype=jnp.float32):
    # fan-in scaled truncated normal (MaxText-style default)
    stddev = scale / max(1.0, (shape[-2] if len(shape) >= 2 else shape[-1])) ** 0.5
    return stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def linear_init(key, d_in, d_out, *, stack=None, dtype=jnp.float32,
                pspec=(FSDP, TP)):
    shape = (d_in, d_out) if stack is None else (stack, d_in, d_out)
    w = truncated_normal_init(key, shape, 1.0, dtype)
    spec = P(*(((None,) * (len(shape) - 2)) + tuple(pspec)))
    return w, spec


def embed_init(key, vocab, d, *, dtype=jnp.float32):
    w = truncated_normal_init(key, (vocab, d), 1.0, dtype)
    return w, P(TP, None)


def norm_init(d, *, stack=None, dtype=jnp.float32):
    shape = (d,) if stack is None else (stack, d)
    w = jnp.ones(shape, dtype)
    return w, P(*((None,) * len(shape)))


def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: Array, scale: Array, bias: Array | None = None,
               eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def rope(x: Array, positions: Array, theta: float = 10000.0,
         rope_dim: int | None = None) -> Array:
    """Rotary embedding. x: (..., S, H, hd) or (..., S, hd); positions (..., S)."""
    hd = x.shape[-1]
    rd = rope_dim or hd
    half = rd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    if x.ndim == ang.ndim + 1:  # head dim present
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xr = x[..., :rd].astype(jnp.float32)
    x1, x2 = xr[..., :half], xr[..., half:]
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rotated.astype(x.dtype), x[..., rd:]], axis=-1)


def sp_out_proj(h: Array, w: Array, specs, fallback_spec) -> Array:
    """Feature-contracting out-projection with an EXPLICIT reduce-scatter.

    h: (B, S, f) with f tp-sharded; w: (f, d). The auto-SPMD lowering of
    ``einsum + sharding_constraint`` emits all-reduce + slice (the ar->rs
    rewrite is a TPU-pipeline pass we cannot rely on); this shard_map issues
    ``psum_scatter`` over the sequence dim directly — (tp-1)/tp fewer bytes
    on the wire per call (§Perf iter 5). Falls back to the constrained
    einsum whenever the shapes/mesh don't divide.
    """
    mesh, dp, tp = getattr(specs, "mesh", None), getattr(specs, "dp", None), \
        getattr(specs, "tp", None)
    B, S, f = h.shape
    d = w.shape[-1]
    if mesh is None or tp is None:
        return maybe_shard(jnp.einsum("bsf,fd->bsd", h, w), fallback_spec)
    tp_n = int(mesh.shape[tp])
    dp_axes = dp if isinstance(dp, tuple) else ((dp,) if dp else ())
    import numpy as _np
    dp_n = int(_np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
    if tp_n <= 1 or S % tp_n or f % tp_n:
        return maybe_shard(jnp.einsum("bsf,fd->bsd", h, w), fallback_spec)
    bdim = dp if (dp_axes and B % dp_n == 0) else None

    def local(h_loc, w_loc):
        y = jnp.einsum("bsf,fd->bsd", h_loc, w_loc)   # partial sum over f
        return jax.lax.psum_scatter(y, tp, scatter_dimension=1, tiled=True)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(bdim, None, tp), P(tp, None)),
        out_specs=P(bdim, tp, None),
        check_vma=False,
    )(h, w)


def maybe_shard(x: Array, spec) -> Array:
    """Shape-aware with_sharding_constraint.

    No-op without a mesh context (single-device tests); under a mesh, spec
    entries whose axis product does not divide the dim fall back to
    replication (e.g. whisper's 1500-frame encoder under 16-way SP).
    """
    if spec is None or not isinstance(spec, P) or all(e is None for e in spec):
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    fixed = []
    for i, e in enumerate(spec):
        if e is not None and i < x.ndim:
            axes = (e,) if isinstance(e, str) else tuple(e)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            if x.shape[i] % size != 0:
                e = None
        fixed.append(e)
    if all(e is None for e in fixed):
        return x
    return jax.lax.with_sharding_constraint(x, P(*fixed))


def resolve_pspec(tree: Any, *, fsdp_axes, tp_axis) -> Any:
    """Map FSDP/TP placeholders in a pspec tree to concrete mesh axes."""

    def fix(spec):
        if not isinstance(spec, P):
            return spec
        out = []
        for e in spec:
            if e == FSDP:
                out.append(fsdp_axes)
            elif e == TP:
                out.append(tp_axis)
            else:
                out.append(e)
        return P(*out)

    return jax.tree.map(fix, tree, is_leaf=lambda s: isinstance(s, P))
