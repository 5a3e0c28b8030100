"""The control of ``correct``: the reference put in the program's place,
one precision step below the configuration's (float32 products at
``"highest"`` -> three bfloat16 passes, ``bf16x3``), compared with the
float64 reference exactly as a run's answers are. It has to come out
as not correct; the program's own numbers on the same answers are
printed beside it, and for a fit the numbers of two faults planted in
the program's answers (its state left unchanged; half of its shards
left unswept).

    python3 chipbench/harness/control.py <workload> <seed> [<seed> ...]

runs at the cell's own size (a fit: ``iterations`` steps of the cell's
MCMCDriver from a fresh chain; serving: one chunk per op through
``serve``), one JSON line per seed. The benchmark's own runs never run
it; ``tests/test_control.py`` runs it at a small size.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

import numpy as np

if __name__ == "__main__":
    _root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path[:0] = [_root, os.path.join(_root, "src")]

from chipbench.harness import bench  # noqa: E402
from chipbench.reference import compare  # noqa: E402

CONTROL = "bf16x3"


def fit_control(jax, cell, seed: int, iterations: int = 12) -> dict:
    from chipbench.harness import fit

    smp = cell.config["sampler"]
    with tempfile.TemporaryDirectory(prefix="chipbench-control-") as tmp:
        drv, Xs, _ = fit.setup(jax, cell, seed, tmp)
        sampler, steps = drv.sampler, []
        step = sampler.step

        def keep(gs, st):
            out = step(gs, st)
            steps.append((gs, st, out))
            return out

        sampler.step = keep
        drv.run(n_iters=iterations)
        rng = np.random.default_rng(seed)
        picks = rng.choice(len(steps), size=min(
            cell.traffic["check_iterations"], len(steps)), replace=False)
        pairs = [(fit._state(jax, sampler, gs, st),
                  fit._state(jax, sampler, *out))
                 for gs, st, out in (steps[i] for i in sorted(picks))]
    out = {"program": {}, "control": {}, "unchanged": {}, "half_batch": {}}
    args = (cell.config["hypers"], smp["L"], smp["K_tail"], smp["P"])
    for s_in, s_out in pairs:
        ctl = compare.replay(CONTROL, Xs, s_in, *args)
        # the faults a fit can have, planted in what the program answered:
        # its state left unchanged; half of its shards' sweeps left out
        half = dict(s_out, Z=s_out["Z"].copy())
        h = half["Z"].shape[0] // 2
        half["Z"][h:] = s_in["Z"][h:]
        for side, st in (("program", s_out), ("control", ctl),
                         ("unchanged", s_in), ("half_batch", half)):
            got = compare.fit_numbers(Xs, s_in, st, *args)
            out[side] = {k: max(out[side].get(k, 0.0), v)
                         for k, v in got.items()}
    return out


def serve_control(jax, cell, seed: int) -> dict:
    from chipbench.harness import serve
    from repro.launch import serve_ibp

    tr = cell.traffic
    bank, smp, K = serve.build_bank(cell, seed)
    traffic = serve.Traffic(cell, seed)
    dims = dict(S=cell.config["bank"]["S"], K=K, n_sweeps=tr["n_sweeps"])
    make_op, calls = serve_ibp.make_op, []

    def recording(bank_, op, n_sweeps):
        fn = make_op(bank_, op, n_sweeps)

        def score(Xp, Mp, key):
            if isinstance(Xp, np.ndarray):
                calls.append((Xp, Mp, key))
            return fn(Xp, Mp, key)
        return score

    serve_ibp.make_op = recording
    rng = np.random.default_rng([seed, 5])
    gaps = {"program": [], "control": []}
    try:
        for op in sorted(set(traffic.cycle)):
            c = traffic.cycle.index(op)
            calls.clear()
            reqs = traffic.chunk(c)
            served, _ = serve_ibp.serve(bank, reqs, op, tr["batch"],
                                        tr["n_sweeps"],
                                        int(traffic.chunk_seeds[c]))
            pick = rng.choice(len(calls), size=min(
                tr["check_microbatches"], len(calls)), replace=False)
            kept = [calls[i] for i in sorted(pick)]
            ref = serve.reference_rows("f64", smp, op, kept, **dims)
            ctl = serve.reference_rows(CONTROL, smp, op, kept, **dims)
            for ans, keys in serve.matched(reqs, served, ref):
                want = np.stack([ref[k] for k in keys])
                gaps["program"].append(compare.answer_gap(ans, want))
                gaps["control"].append(compare.answer_gap(
                    np.stack([ctl[k] for k in keys]), want))
    finally:
        serve_ibp.make_op = make_op
    return {side: compare.serve_numbers(g) for side, g in gaps.items()}


def main(argv: list[str]) -> int:
    cell = bench.resolve(argv[0])
    jax = bench.setup_jax(cell.config)
    bench.require_chips(jax, cell.chips)
    run = fit_control if cell.traffic["kind"] == "fit" else serve_control
    for seed in argv[1:]:
        got = run(jax, cell, int(seed))
        print(json.dumps({"workload": cell.name, "seed": int(seed), **got}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
