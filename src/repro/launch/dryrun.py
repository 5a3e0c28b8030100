import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent on the
production mesh without hardware: jit(step).lower(**ShapeDtypeStructs)
.compile() must succeed; we record memory_analysis, cost_analysis, and the
collective bytes parsed from the partitioned HLO into
artifacts/dryrun/<arch>__<shape>__<mesh>.json (incremental: existing cells
are skipped unless --force).

Usage:
  python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k --mesh pod1
  python -m repro.launch.dryrun --all [--mesh pod1|pod2|both]
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ALL_SHAPES, ARCH_IDS, get_config, shape_applicable
from repro.launch.specs import (
    abstract_caches,
    abstract_model,
    input_specs,
    param_bytes,
)
from repro.models import make_decode_step, make_prefill_step, make_train_step
from repro.optim import AdamW
from repro.parallel.mesh import (
    act_specs,
    batch_specs,
    cache_specs,
    make_production_mesh,
    named,
    resolve_param_specs,
)

ARTIFACTS = os.path.join(os.path.dirname(__file__), "../../../artifacts/dryrun")

COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(tok: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(tok):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-device bytes moved by collectives, from the partitioned HLO.

    We sum RESULT shapes: for all-gather that is the gathered (full) size,
    for all-reduce the reduced operand size, for reduce-scatter the shard —
    a uniform, slightly conservative proxy for bytes-on-the-wire.
    """
    out: dict[str, int] = {k: 0 for k in COLLECTIVE_OPS}
    out["counts"] = {k: 0 for k in COLLECTIVE_OPS}  # type: ignore[assignment]
    for line in hlo_text.splitlines():
        ls = line.strip()
        if "=" not in ls:
            continue
        for op in COLLECTIVE_OPS:
            # match '= <shape> op(' including fused variants like
            # 'all-reduce-start('
            m = re.search(rf"= (.*?) {op}(?:-start)?\(", ls)
            if m:
                out[op] += _shape_bytes(m.group(1))
                out["counts"][op] += 1  # type: ignore[index]
                break
    out["total"] = sum(out[k] for k in COLLECTIVE_OPS)
    return out


def cost_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` normalized across JAX versions: 0.4.x
    returns a list with one dict per device program, newer versions the
    dict itself."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost or {}


def build_step(cfg, shape, mesh, force_param_bytes: int | None = None):
    """Returns (jitted_fn, example_args as ShapeDtypeStructs w/ shardings)."""
    serve = shape.mode != "train"
    pstruct, pspecs = abstract_model(cfg, serve=serve)
    pbytes = force_param_bytes or param_bytes(pstruct, 2)
    pspec_r = resolve_param_specs(
        pspecs, pstruct, mesh,
        mode="train" if not serve else "serve",
        param_bytes=pbytes,
    )
    specs = act_specs(
        mesh, seq_len=shape.seq_len, batch=shape.global_batch,
        mode=shape.mode, d_ff=max(cfg.d_ff, 2 * (cfg.d_ff_expert or 0)),
    )
    batch = input_specs(cfg, shape)
    bspec = batch_specs(batch, mesh)

    p_sh = named(mesh, pspec_r)
    b_sh = named(mesh, bspec)

    if shape.mode == "train":
        opt = AdamW(lr=1e-4)
        ostruct = jax.eval_shape(opt.init, pstruct)
        ospec = {
            "m": pspec_r,
            "v": pspec_r,
            "step": jax.sharding.PartitionSpec(),
        }
        o_sh = named(mesh, ospec)
        step = make_train_step(cfg, opt, specs)
        fn = jax.jit(
            step,
            in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, None),
            donate_argnums=(0, 1),
        )
        args = (pstruct, ostruct, batch)
    elif shape.mode == "prefill":
        step = make_prefill_step(cfg, specs)
        fn = jax.jit(step, in_shardings=(p_sh, b_sh))
        args = (pstruct, batch)
    else:
        cstruct = abstract_caches(cfg, shape.global_batch, shape.seq_len)
        cspec = cache_specs(cstruct, mesh)
        c_sh = named(mesh, cspec)
        step = make_decode_step(cfg, specs)
        fn = jax.jit(
            step,
            in_shardings=(p_sh, b_sh, c_sh),
            out_shardings=(None, c_sh),
            donate_argnums=(2,),
        )
        args = (pstruct, batch, cstruct)
    return fn, args


def run_cell(arch: str, shape, mesh_name: str, force: bool = False) -> dict:
    os.makedirs(ARTIFACTS, exist_ok=True)
    out_path = os.path.join(
        ARTIFACTS, f"{arch}__{shape.name}__{mesh_name}.json"
    )
    if os.path.exists(out_path) and not force:
        with open(out_path) as fh:
            return json.load(fh)

    cfg = get_config(arch)
    ok, why = shape_applicable(cfg, shape)
    rec: dict = {
        "arch": arch, "shape": shape.name, "mesh": mesh_name,
        "mode": shape.mode, "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
    }
    if not ok:
        rec.update(status="skipped", reason=why)
        with open(out_path, "w") as fh:
            json.dump(rec, fh, indent=1)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_name == "pod2"))
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            fn, args = build_step(cfg, shape, mesh)
            lowered = fn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = cost_dict(compiled)
            hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        rec.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            flops=float(cost.get("flops", -1.0)) if cost else -1.0,
            bytes_accessed=float(cost.get("bytes accessed", -1.0))
            if cost else -1.0,
            collectives=coll,
            memory={
                k: int(getattr(mem, k))
                for k in (
                    "argument_size_in_bytes",
                    "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "generated_code_size_in_bytes",
                )
                if mem is not None and hasattr(mem, k)
            },
            hlo_bytes=len(hlo),
        )
    except Exception as e:  # a failing cell is a bug — record it loudly
        rec.update(
            status="error",
            error=f"{type(e).__name__}: {e}",
            traceback=traceback.format_exc()[-4000:],
        )
    with open(out_path, "w") as fh:
        json.dump(rec, fh, indent=1)
    return rec


def run_ibp_cell(mesh_name: str, *, N: int = 1 << 20, D: int = 36,
                 K_max: int = 64, K_tail: int = 8, L: int = 5,
                 force: bool = False, tag: str = "mcmc_1m",
                 sync: str = "staged") -> dict:
    """Lower the paper's hybrid sampler itself on the production mesh: 2^20
    observations sharded over every chip (the paper's P processors = 256/512),
    Cambridge dimensionality. This is the 'most representative of the paper's
    technique' roofline/hillclimb cell (§Perf cell 3)."""
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.ibp import IBPHypers, SamplerSpec, build_hybrid_fns

    os.makedirs(ARTIFACTS, exist_ok=True)
    name = f"ibp-hybrid__{tag}" + ("" if sync == "staged" else f"-{sync}")
    out_path = os.path.join(ARTIFACTS, f"{name}__{mesh_name}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as fh:
            return json.load(fh)

    mesh = make_production_mesh(multi_pod=(mesh_name == "pod2"))
    axes = tuple(mesh.axis_names)            # every chip is one processor p
    P_ = int(np.prod([mesh.shape[a] for a in axes]))
    rec: dict = {
        "arch": "ibp-hybrid", "shape": tag, "mesh": mesh_name,
        "mode": "mcmc", "seq_len": D, "global_batch": N, "sync": sync,
        "P": P_, "K_max": K_max, "K_tail": K_tail, "L": L,
    }
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            # every production mesh axis is a data axis here (flattened
            # into the paper's P processors); no chain axis in this cell
            spec = SamplerSpec(P=P_, L=L, K_max=K_max, K_tail=K_tail,
                               data="shardmap", sync=sync)
            step = build_hybrid_fns(spec, IBPHypers(), N_global=N,
                                    mesh=mesh, data_axes=axes).step
            f32 = jnp.float32
            row_sh = NamedSharding(mesh, P(axes))
            rep = NamedSharding(mesh, P())

            def rs(shape):
                return jax.ShapeDtypeStruct(shape, f32, sharding=row_sh)

            from repro.core.ibp.hybrid import HybridGlobal
            gs = HybridGlobal(
                A=jax.ShapeDtypeStruct((K_max, D), f32, sharding=rep),
                pi=jax.ShapeDtypeStruct((K_max,), f32, sharding=rep),
                active=jax.ShapeDtypeStruct((K_max,), f32, sharding=rep),
                alpha=jax.ShapeDtypeStruct((), f32, sharding=rep),
                sigma_x=jax.ShapeDtypeStruct((), f32, sharding=rep),
                sigma_a=jax.ShapeDtypeStruct((), f32, sharding=rep),
                key=jax.ShapeDtypeStruct(
                    (), jax.random.key(0).dtype, sharding=rep),
                p_prime=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
                it=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
                overflow=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
                tail_sat=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
                tail_refresh=jax.ShapeDtypeStruct((), jnp.int32,
                                                  sharding=rep),
            )
            args = (rs((N, D)), gs, rs((N, K_max)), rs((N, K_tail)),
                    jax.ShapeDtypeStruct((P_, K_tail), f32, sharding=row_sh))
            lowered = step.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = cost_dict(compiled)
            hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        rec.update(
            status="ok",
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            flops=float(cost.get("flops", -1.0)) if cost else -1.0,
            bytes_accessed=float(cost.get("bytes accessed", -1.0))
            if cost else -1.0,
            collectives=coll,
            memory={
                k: int(getattr(mem, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes")
                if mem is not None and hasattr(mem, k)
            },
            hlo_bytes=len(hlo),
        )
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    with open(out_path, "w") as fh:
        json.dump(rec, fh, indent=1)
    return rec


def _probe_depths(cfg) -> tuple[int, int]:
    """Layer counts for the two depth probes (pattern-preserving)."""
    if cfg.family == "hybrid":
        p = len(cfg.rglru_pattern or ("rec", "rec", "attn"))
        return p, 2 * p
    return 1, 2


def run_probe(arch: str, shape, mesh_name: str, force: bool = False) -> dict:
    """Lower reduced-depth variants to measure the per-layer marginal cost.

    XLA-CPU cost_analysis counts a while-loop body once regardless of trip
    count, so full-depth HLO flops/bytes under scan-over-layers are
    undercounted. The roofline reader extrapolates:
        total ~= probe(L1) + (L - L1) / (L2 - L1) * (probe(L2) - probe(L1)).
    Probes run with the FULL model's param-byte budget so the serve
    FSDP decision (and hence the collective pattern) matches the real cell.
    """
    import dataclasses

    os.makedirs(ARTIFACTS, exist_ok=True)
    out_path = os.path.join(
        ARTIFACTS, f"probe__{arch}__{shape.name}__{mesh_name}.json"
    )
    if os.path.exists(out_path) and not force:
        with open(out_path) as fh:
            return json.load(fh)

    cfg = get_config(arch)
    ok, why = shape_applicable(cfg, shape)
    rec: dict = {"arch": arch, "shape": shape.name, "mesh": mesh_name}
    if not ok:
        rec.update(status="skipped", reason=why)
        with open(out_path, "w") as fh:
            json.dump(rec, fh, indent=1)
        return rec

    pstruct, _ = abstract_model(cfg, serve=shape.mode != "train")
    full_pbytes = param_bytes(pstruct, 2)
    mesh = make_production_mesh(multi_pod=(mesh_name == "pod2"))
    L1, L2 = _probe_depths(cfg)
    probes = {}
    try:
        for L in (L1, L2):
            # unroll: XLA cost_analysis counts a lax.scan body ONCE regardless
            # of trip count, so probes must unroll for the L2-L1 marginal to be
            # the true per-layer cost (roofline extrapolation depends on it)
            sub = {"n_layers": L, "unroll_layers": True}
            if cfg.family == "encdec":
                sub["n_enc_layers"] = L
            cfg_l = dataclasses.replace(cfg, **sub)
            with jax.set_mesh(mesh):
                fn, args = build_step(
                    cfg_l, shape, mesh, force_param_bytes=full_pbytes
                )
                compiled = fn.lower(*args).compile()
                cost = cost_dict(compiled)
                hlo = compiled.as_text()
            coll = collective_bytes(hlo)
            probes[str(L)] = {
                "flops": float(cost.get("flops", -1.0)),
                "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
                "collective_total": coll["total"],
            }
        rec.update(status="ok", L1=L1, L2=L2, probes=probes)
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    with open(out_path, "w") as fh:
        json.dump(rec, fh, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=[s.name for s in ALL_SHAPES])
    ap.add_argument("--mesh", choices=["pod1", "pod2", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--probe", action="store_true",
                    help="lower reduced-depth variants for roofline "
                         "extrapolation instead of the full cells")
    ap.add_argument("--ibp", action="store_true",
                    help="lower the IBP hybrid-sampler cell (2^20 rows over "
                         "all chips) instead of LM cells")
    ap.add_argument("--sync", choices=["staged", "fused"], default="staged")
    args = ap.parse_args()

    meshes = ["pod1", "pod2"] if args.mesh == "both" else [args.mesh]

    if args.ibp:
        bad = 0
        for mesh_name in meshes:
            rec = run_ibp_cell(mesh_name, force=args.force, sync=args.sync)
            extra = ""
            if rec["status"] == "ok":
                c = rec["collectives"]
                extra = (f"compile={rec['compile_s']}s "
                         f"AR_count={c['counts']['all-reduce']} "
                         f"coll={c['total'] / 2**20:.2f}MiB "
                         f"flops={rec['flops']:.3g}")
            elif rec["status"] == "error":
                extra = rec["error"][:200]
            print(f"[{rec['status']:7s}] ibp-hybrid ({args.sync:6s}) "
                  f"{mesh_name} {extra}", flush=True)
            bad += rec["status"] == "error"
        return 1 if bad else 0
    archs = ARCH_IDS if args.all or not args.arch else [args.arch]
    shapes = (
        ALL_SHAPES
        if args.all or not args.shape
        else [s for s in ALL_SHAPES if s.name == args.shape]
    )

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                if args.probe:
                    rec = run_probe(arch, shape, mesh_name, force=args.force)
                    print(f"[{rec['status']:7s}] probe {arch:24s} "
                          f"{shape.name:12s} {mesh_name}", flush=True)
                    n_ok += rec["status"] == "ok"
                    n_skip += rec["status"] == "skipped"
                    n_err += rec["status"] == "error"
                    continue
                rec = run_cell(arch, shape, mesh_name, force=args.force)
                tag = rec["status"]
                n_ok += tag == "ok"
                n_skip += tag == "skipped"
                n_err += tag == "error"
                extra = ""
                if tag == "ok":
                    mem_gb = rec["memory"].get("temp_size_in_bytes", 0) / 2**30
                    extra = (
                        f"compile={rec['compile_s']}s flops/dev="
                        f"{rec['flops']:.3g} coll/dev="
                        f"{rec['collectives']['total'] / 2**20:.1f}MiB "
                        f"temp={mem_gb:.2f}GiB"
                    )
                elif tag == "error":
                    extra = rec["error"][:160]
                print(f"[{tag:7s}] {arch:24s} {shape.name:12s} {mesh_name} {extra}",
                      flush=True)
    print(f"\nok={n_ok} skipped={n_skip} error={n_err}")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
