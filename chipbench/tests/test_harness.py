"""The harness finds every cell's files by name, the data generator is
pinned, and a run off a TPU prints no result."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench.harness import bench, cambridge

ROOT = bench.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_its_files(name):
    cell = bench.resolve(name)
    assert cell.config["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == name)
    assert bench.runner(cell.traffic["kind"]).run
    assert set(cell.limits["numbers"]) and all(
        v > 0 for v in cell.limits["numbers"].values())
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(bench.metric_reader(m["name"]))


def test_configs_and_metrics_are_consistent():
    for c in BENCH["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"]
    names = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in names
        for w in m["workloads"]:
            e2e = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
            assert w in e2e.get("workloads", CELLS)


def test_cambridge_copy_is_pinned():
    X, Z = cambridge.cambridge(64, 0.5, 12345)
    assert hashlib.sha256(X.tobytes()).hexdigest() == (
        "d792681ebeab47cab09616611fc72639099d02ac9f78122b4fe39ce7547fcdfb")
    assert Z.shape == (64, 4) and set(np.unique(Z)) <= {0.0, 1.0}
    assert cambridge.features().sum(axis=1).tolist() == [8.0, 6.0, 6.0, 7.0]


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def _printed_result(stdout: str) -> bool:
    return any(line.startswith("{") for line in stdout.splitlines())


def test_run_off_tpu_exits_without_result():
    p = _run(ROOT, "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0
    assert not _printed_result(p.stdout)
    assert "TPU" in p.stderr


def test_run_with_only_the_benchmark_files_exits_without_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".jax_cache",
                                                  "__pycache__"))
    p = _run(tmp_path, "--workload", CELLS[0], "--seed", "2", "--seconds",
             "1", "--trace", "0")
    assert p.returncode != 0
    assert not _printed_result(p.stdout)
