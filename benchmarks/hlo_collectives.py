"""Diagnostic: rank collectives in a cell's partitioned HLO by bytes.

    PYTHONPATH=src python -m benchmarks.hlo_collectives --arch deepseek-v2-236b \
        --shape train_4k [--layers 1]

Lowers the cell at a reduced UNROLLED depth (so every per-layer collective is
visible and attributable) and prints per-op byte totals grouped by (op kind,
result shape, source op_name metadata) — the profile §Perf iterates on.
"""
from __future__ import annotations

import argparse
import re
from collections import defaultdict

from repro.launch import dryrun


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="pod1")
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args()

    import dataclasses
    import jax
    from repro.configs import ALL_SHAPES, get_config
    from repro.launch.specs import abstract_model, param_bytes
    from repro.parallel.mesh import make_production_mesh

    shape = next(s for s in ALL_SHAPES if s.name == args.shape)
    cfg = get_config(args.arch)
    pstruct, _ = abstract_model(cfg, serve=shape.mode != "train")
    full_pbytes = param_bytes(pstruct, 2)
    sub = {"n_layers": args.layers, "unroll_layers": True}
    if cfg.family == "encdec":
        sub["n_enc_layers"] = args.layers
    cfg_l = dataclasses.replace(cfg, **sub)
    mesh = make_production_mesh(multi_pod=(args.mesh == "pod2"))
    with jax.set_mesh(mesh):
        fn, fargs = dryrun.build_step(cfg_l, shape, mesh,
                                      force_param_bytes=full_pbytes)
        hlo = fn.lower(*fargs).compile().as_text()

    groups: dict[tuple, list] = defaultdict(lambda: [0, 0])
    for line in hlo.splitlines():
        ls = line.strip()
        if "=" not in ls:
            continue
        for op in dryrun.COLLECTIVE_OPS:
            m = re.search(rf"= (.*?) {op}(?:-start)?\(", ls)
            if not m:
                continue
            nbytes = dryrun._shape_bytes(m.group(1))
            mm = re.search(r'op_name="([^"]*)"', ls)
            src = mm.group(1) if mm else "?"
            src = re.sub(r"/while/body", "", src)[:110]
            key = (op, m.group(1)[:48], src)
            groups[key][0] += nbytes
            groups[key][1] += 1
            break

    rows = sorted(groups.items(), key=lambda kv: -kv[1][0])
    total = sum(v[0] for v in groups.values())
    print(f"{args.arch} x {args.shape} @ {args.mesh}, {args.layers} layer(s) "
          f"unrolled — total collective result-bytes/dev: {total / 2**30:.2f} GiB")
    print(f"{'GiB':>8} {'n':>4}  kind             shape / source")
    for (op, shp, src), (b, n) in rows[: args.top]:
        print(f"{b / 2**30:8.3f} {n:4d}  {op:16s} {shp}")
        print(f"{'':14}{src}")


if __name__ == "__main__":
    main()
