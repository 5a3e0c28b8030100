"""Perf-hillclimb harness (§Perf): measure a cell's roofline terms under
config overrides and log hypothesis -> before/after to artifacts/perf_log.jsonl.

    PYTHONPATH=src python -m benchmarks.hillclimb --arch deepseek-v2-236b \
        --shape train_4k --tag moe_gather --set moe_impl=gather
    PYTHONPATH=src python -m benchmarks.hillclimb --arch deepseek-v2-236b \
        --shape train_4k --tag moe_a2a --set moe_impl=a2a

Measurement = the same probe-extrapolation the roofline table uses (two
reduced UNROLLED depths; per-layer marginal x full depth), so before/after
deltas are apples-to-apples with §Roofline.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9


def measure(arch: str, shape_name: str, overrides: dict, mesh_name="pod1"):
    import jax
    from repro.configs import ALL_SHAPES, get_config
    from repro.launch import dryrun
    from repro.launch.specs import abstract_model, param_bytes
    from repro.parallel.mesh import make_production_mesh

    shape = next(s for s in ALL_SHAPES if s.name == shape_name)
    cfg = get_config(arch)
    pstruct, _ = abstract_model(cfg, serve=shape.mode != "train")
    full_pbytes = param_bytes(pstruct, 2)
    L_full = cfg.n_layers
    L1, L2 = dryrun._probe_depths(cfg)
    mesh = make_production_mesh(multi_pod=(mesh_name == "pod2"))
    probes = {}
    for L in (L1, L2):
        sub = dict(overrides, n_layers=L, unroll_layers=True)
        if cfg.family == "encdec":
            sub["n_enc_layers"] = L
        cfg_l = dataclasses.replace(cfg, **sub)
        t0 = time.time()
        with jax.set_mesh(mesh):
            fn, args = dryrun.build_step(cfg_l, shape, mesh,
                                         force_param_bytes=full_pbytes)
            compiled = fn.lower(*args).compile()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        coll = dryrun.collective_bytes(hlo)
        probes[L] = {
            "flops": float(cost.get("flops", -1.0)),
            "bytes": float(cost.get("bytes accessed", -1.0)),
            "coll": float(coll["total"]),
            "coll_by_kind": {k: coll[k] for k in dryrun.COLLECTIVE_OPS},
            "compile_s": round(time.time() - t0, 1),
        }
    out = {}
    for key in ("flops", "bytes", "coll"):
        a, b = probes[L1][key], probes[L2][key]
        slope = max((b - a) / (L2 - L1), 0.0)
        out[key] = a + (L_full - L1) * slope
    terms = {
        "compute_s": out["flops"] / PEAK_FLOPS,
        "memory_s": out["bytes"] / HBM_BW,
        "collective_s": out["coll"] / LINK_BW,
    }
    terms["t_star_s"] = max(terms.values())
    terms["dominant"] = max(terms, key=lambda k: terms[k]
                            if k.endswith("_s") and k != "t_star_s" else -1)
    # per-kind collective extrapolation for the dominant-term breakdown
    kinds = {}
    for k in probes[L1]["coll_by_kind"]:
        a = probes[L1]["coll_by_kind"][k]
        b = probes[L2]["coll_by_kind"][k]
        kinds[k] = a + (L_full - L1) * max((b - a) / (L2 - L1), 0.0)
    return {"probes": {str(k): v for k, v in probes.items()},
            "extrapolated": out, "terms": terms, "coll_by_kind": kinds}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="pod1")
    ap.add_argument("--tag", required=True)
    ap.add_argument("--hypothesis", default="")
    ap.add_argument("--set", nargs="*", default=[],
                    help="cfg overrides: key=value (int/float/str inferred)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        overrides[k] = v

    rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
           "tag": args.tag, "hypothesis": args.hypothesis,
           "overrides": overrides}
    rec.update(measure(args.arch, args.shape, overrides, args.mesh))

    os.makedirs(ART, exist_ok=True)
    with open(os.path.join(ART, "perf_log.jsonl"), "a") as fh:
        fh.write(json.dumps(rec) + "\n")

    t = rec["terms"]
    print(f"\n[{args.tag}] {args.arch} x {args.shape} @ {args.mesh}")
    print(f"  compute    {t['compute_s']:10.3f} s")
    print(f"  memory     {t['memory_s']:10.3f} s")
    print(f"  collective {t['collective_s']:10.3f} s   <- breakdown:")
    for k, v in sorted(rec["coll_by_kind"].items(), key=lambda kv: -kv[1]):
        if v > 0:
            print(f"      {k:20s} {v / 2**30:10.2f} GiB")
    print(f"  T* = {t['t_star_s']:.3f} s  dominant = {t['dominant']}")


if __name__ == "__main__":
    main()
