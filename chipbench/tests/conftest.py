"""Helpers for driving the benchmark on the CPU at a small size: the
chip check is skipped, and JAX's global configuration (compile cache,
matmul precision) is left as the test session has it."""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# cell -> (configuration, traffic, chips, how it is shrunk for the CPU)
CELLS = {
    "paper-fit": ("cambridge-paper", "fit", 1,
                  lambda c: c.config["data"].update(N=200)),
    "dp4-fit": ("cambridge-dp4", "fit", 4,
                lambda c: c.config["data"].update(N=200)),
    "paper-serve-batch": ("cambridge-paper", "serve-batch", 1,
                          lambda c: (c.config["bank"].update(S=8),
                                     c.traffic.update(chunk_requests=48))),
}


def load_cell(name: str):
    """The cell from its files under ``chipbench/``, whether or not
    BENCHMARK.json lists it."""
    import json

    from chipbench.harness import bench

    config, traffic, chips, _ = CELLS[name]

    def load(*part):
        with open(os.path.join(bench.BENCH_DIR, *part)) as fh:
            return json.load(fh)
    return bench.Cell(name, chips, load("configs", config + ".json"),
                      load("traffic", traffic + ".json"),
                      load("limits", name + ".json"), [], [])


@pytest.fixture
def small_cell(monkeypatch):
    """``small_cell(name)``: the cell, shrunk, with the chip check off."""
    import jax

    from chipbench.harness import bench

    monkeypatch.setattr(bench, "setup_jax", lambda config: jax)
    monkeypatch.setattr(bench, "require_chips", lambda jax, chips: None)

    def make(name: str):
        cell = load_cell(name)
        CELLS[name][3](cell)
        return cell
    return make
