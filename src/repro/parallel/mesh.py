"""Production mesh + sharding-rule resolution.

Mesh: (data=16, model=16) = 256 chips/pod; multi-pod adds a leading pod=2
axis (512 chips). Defined as FUNCTIONS — importing this module never touches
jax device state (required: only dryrun.py forces 512 host devices).

Sharding rules (DESIGN.md §6):
  train  — FSDP: weights/optimizer shard over (pod, data) x model;
           activations batch->data(+pod), sequence->model (Megatron-SP at
           block boundaries), TP on projections/experts.
  serve  — TP only; weights additionally shard over data if the per-chip
           bf16 footprint exceeds the HBM budget (inference-FSDP, e.g.
           deepseek-v2).

Every placement is divisibility-checked against the mesh: a dim that does
not divide falls back to replication for that dim (never a compile error —
e.g. smollm's 9 heads never shard over model=16; its flattened QKV features
do).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.modules import FSDP, TP
from repro.models.transformer import ActSpecs

HBM_BYTES = 16 * 1024**3          # TPU v5e: 16 GB
SERVE_WEIGHT_BUDGET = 9 * 1024**3  # leave headroom for caches/activations


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes)
    )


def mesh_axes(mesh: Mesh) -> dict[str, Any]:
    multi = "pod" in mesh.axis_names
    dp = ("pod", "data") if multi else ("data",)
    return {
        "dp": dp,
        "tp": "model",
        "dp_size": int(np.prod([mesh.shape[a] for a in dp])),
        "tp_size": int(mesh.shape["model"]),
    }


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return int(mesh.shape[axes])
    return int(np.prod([mesh.shape[a] for a in axes]))


def _fit(spec_entry, dim: int, mesh: Mesh):
    """Keep a spec entry only if the dim divides the axis product."""
    if spec_entry is None:
        return None
    return spec_entry if dim % _axis_size(mesh, spec_entry) == 0 else None


def _resolve_leaf_spec(spec: P, shape, mesh, fsdp_axes, tp_axis) -> P:
    out = []
    for i, e in enumerate(spec):
        if e == FSDP:
            e = fsdp_axes
        elif e == TP:
            e = tp_axis
        if e is not None and i < len(shape):
            e = _fit(e, shape[i], mesh)
        out.append(e)
    return P(*out)


def resolve_param_specs(spec_tree, shape_tree, mesh, *, mode: str,
                        param_bytes: int = 0):
    """Map FSDP/TP placeholders to mesh axes with divisibility fallback."""
    ax = mesh_axes(mesh)
    if mode == "train":
        fsdp: Any = ax["dp"] if len(ax["dp"]) > 1 else ax["dp"][0]
    else:
        # inference-FSDP only when TP-sharded weights would blow HBM
        per_chip = param_bytes / ax["tp_size"]
        fsdp = (
            (ax["dp"] if len(ax["dp"]) > 1 else ax["dp"][0])
            if per_chip > SERVE_WEIGHT_BUDGET
            else None
        )

    def fix(spec, shape):
        return _resolve_leaf_spec(spec, shape.shape, mesh, fsdp, ax["tp"])

    return jax.tree.map(
        fix, spec_tree, shape_tree, is_leaf=lambda s: isinstance(s, P)
    )


def act_specs(mesh: Mesh, *, seq_len: int, batch: int, mode: str,
              d_ff: int = 0) -> ActSpecs:
    ax = mesh_axes(mesh)
    dp = ax["dp"] if len(ax["dp"]) > 1 else ax["dp"][0]
    bdim = dp if batch % ax["dp_size"] == 0 else None
    # sequence-parallel residual stream in train (bounds the remat carry)
    sp = (
        ax["tp"]
        if mode == "train" and seq_len % ax["tp_size"] == 0
        else None
    )
    # MLP schedule (§Perf iter 3): Megatron-TP moves ~2·T_full·d activation
    # bytes/layer; ZeRO-3-style weight gathering moves ~3·d·ff. Choose dp
    # when the token side dominates (full-seq tokens per data shard).
    t_full = (batch // ax["dp_size"] if bdim else batch) * seq_len
    mlp_dp = d_ff > 0 and t_full > 1.5 * d_ff
    return ActSpecs(
        hid=P(bdim, sp, None),
        feat=P(bdim, None, ax["tp"]),
        exp=P(ax["tp"], bdim, None),
        logits=P(bdim, None, ax["tp"]),
        mesh=mesh,
        dp=dp,
        tp=ax["tp"],
        mlp_dp=mlp_dp,
    )


def batch_specs(batch_struct, mesh: Mesh) -> Any:
    """tokens/labels (B, S) -> P(dp, None); embeddings (B, S, d) likewise."""
    ax = mesh_axes(mesh)
    dp = ax["dp"] if len(ax["dp"]) > 1 else ax["dp"][0]

    def fix(x):
        bdim = dp if x.shape and x.shape[0] % ax["dp_size"] == 0 else None
        return P(*([bdim] + [None] * (len(x.shape) - 1)))

    return jax.tree.map(fix, batch_struct)


def cache_specs(cache_struct, mesh: Mesh) -> Any:
    """Stacked caches (L, B, ..., D_last): batch->dp, innermost divisible of
    the last two dims -> model, rest replicated."""
    ax = mesh_axes(mesh)
    dp = ax["dp"] if len(ax["dp"]) > 1 else ax["dp"][0]
    tp = ax["tp"]
    tp_n = ax["tp_size"]

    def fix(x):
        nd = len(x.shape)
        if nd <= 1:
            return P()
        spec = [None] * nd
        # batch axis: stacked caches have it at 1, unstacked at 0
        for b_ax in (1, 0):
            if b_ax < nd - 1 and x.shape[b_ax] % ax["dp_size"] == 0 and \
                    x.shape[b_ax] > 1:
                spec[b_ax] = dp
                break
        if x.shape[-1] % tp_n == 0:
            spec[-1] = tp
        elif nd >= 2 and x.shape[-2] % tp_n == 0 and spec[nd - 2] is None:
            spec[-2] = tp
        return P(*spec)

    return jax.tree.map(fix, cache_struct)


def named(mesh: Mesh, spec_tree) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def resolve_shardings(cfg, shape_cfg, mesh: Mesh):
    """One-stop: (param specs fn, act specs, batch/cache spec fns) per cell."""
    return {
        "act": act_specs(
            mesh, seq_len=shape_cfg.seq_len, batch=shape_cfg.global_batch,
            mode=shape_cfg.mode, d_ff=cfg.d_ff,
        ),
        "axes": mesh_axes(mesh),
    }
