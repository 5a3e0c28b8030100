"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's configuration, traffic and limits are found by name
(``harness/bench.py``). Set-up generates data from the seed, builds the
system and compiles what the window runs; the window runs for about
``--seconds``; then the answers are compared with the plain reference
(``reference/``). ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the window.
Off a TPU, or short of the chips the cell asks for, it exits non-zero
and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from chipbench.harness import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = bench.resolve(args.workload)
    runner = bench.runner(cell.traffic["kind"])
    result, checks, info = runner.run(cell, args.seed, args.seconds,
                                      bool(args.trace), T_START)
    bench.emit(result, checks, info)
    return 0


if __name__ == "__main__":
    sys.exit(main())
