"""What every cell shares: finding its files by name, the chip check,
the compile cache, the per-layer metric readers and the result line.

A cell (an entry of ``workloads`` in BENCHMARK.json) names a
configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); the mix's ``kind`` names the runner module
(``harness/<kind>.py``) that runs it; each per-layer metric is read by
``metrics/<name>.py``; the limits that decide ``correct`` are in
``limits/<cell>.json``. Adding a cell, a mix of a known kind or a metric
adds files and entries and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")


def _load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list        # the BENCHMARK.json entries this cell reports
    per_layer: list


def resolve(workload: str, bench_path: str | None = None) -> Cell:
    """The cell named ``workload``, with its files and metric entries."""
    bench = _load_json(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[workload]

    def mine(m):
        return workload in m.get("workloads", list(cells))

    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_load_json(os.path.join(BENCH_DIR, "configs",
                                       w["config"] + ".json")),
        traffic=_load_json(os.path.join(BENCH_DIR, "traffic",
                                        w["traffic"] + ".json")),
        limits=_load_json(os.path.join(BENCH_DIR, "limits",
                                       workload + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)],
    )


def runner(kind: str):
    return importlib.import_module(f"chipbench.harness.{kind}")


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read(ctx) -> float | None``."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def setup_jax(config: dict):
    """The compile cache inside the checkout, and the configuration's
    precision. Returns the jax module."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_default_matmul_precision",
                      config["precision"]["matmul"])
    return jax


def require_chips(jax, chips: int) -> None:
    """Exit non-zero, with no result, off a TPU or short of chips."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chipbench: needs {chips} TPU chip(s), JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(1)


def device_info(jax) -> dict:
    devs = jax.devices()
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}


def peak_of(kind: str) -> dict:
    table = _load_json(os.path.join(BENCH_DIR, "harness", "peaks.json"))
    if kind not in table["devices"]:
        raise SystemExit(f"no peak for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def checks_line(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; ``correct`` when every number is at
    most its limit (a missing or non-finite number fails)."""
    out, ok = {}, True
    for name, lim in limits["numbers"].items():
        v = numbers.get(name)
        good = v is not None and v == v and v <= lim
        ok = ok and good
        out[name] = {"value": v, "limit": lim}
    return ok, out


def compile_log(jax) -> list:
    """A list that grows by one entry per backend compile from now on
    (a persistent-cache load counts too: both mean a program was built
    in the process at that point)."""
    seen: list = []

    def on(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(secs)
    jax.monitoring.register_event_duration_secs_listener(on)
    return seen


def emit(result: dict, checks: dict, info: dict | None = None) -> None:
    """The contract's last lines: ``info`` and the compared numbers on
    standard error, then the result as the last line of standard output,
    checks last."""
    for name, v in (info or {}).items():
        print(f"info {name} = {v!r}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    result = dict(result, checks=checks)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def read_metrics(cell: Cell, ctx: dict) -> dict:
    """The cell's per-layer metrics from ``ctx``; a reader that finds
    nothing to read returns None and its metric is left out."""
    out = {}
    for m in cell.per_layer:
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
