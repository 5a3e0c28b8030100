"""From a profiler trace to the numbers per-layer metrics read.

Two steps, kept apart so the second can be checked on a recorded trace:

``events(logdir)``   the ``.xplane.pb`` the JAX profiler wrote, as plain
                     lists: per device, its XLA op events and its program
                     (module) executions; and the host spans this
                     benchmark opened (names ``chipbench:*``).
``reduce(ev, t0, t1)``  per device over the window [t0, t1] (ns): the busy
                     union of all ops, of compute ops (every op that is
                     not a collective), the time inside collectives and
                     the part of it no compute overlaps; then means over
                     devices, the ops that took most time, the longest
                     compute-idle gaps labelled by the innermost
                     benchmark span open at their middle, and how often
                     the program that took most time ran.

A device's trace buffer holds a bounded number of events; where one
filled before t1, the window ends at the last event that device
recorded, so that no unrecorded time reads as idle.
"""
from __future__ import annotations

import glob
import os

import numpy as np

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench:"


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVES)


def _short(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def start(logdir: str) -> None:
    """Start the profiler with host spans and device ops, and without
    the Python function tracer (which slows the host several-fold)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)


def events(logdir: str) -> dict:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {logdir}")
    devices: dict[str, list] = {}
    modules: dict[str, list] = {}
    host: list = []
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                for line in plane.lines:
                    into = {OP_LINE: devices, MODULE_LINE: modules}.get(
                        line.name)
                    if into is not None:
                        into.setdefault(plane.name, []).extend(
                            [_short(e.name), int(e.start_ns),
                             int(e.duration_ns)] for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in line.events
                                if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "modules": modules, "host": host}


def union(iv: np.ndarray) -> np.ndarray:
    """Merged (start, end) intervals, sorted."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.float64)


def length(iv: np.ndarray) -> float:
    return float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0


def minus(a: np.ndarray, b: np.ndarray) -> float:
    """Length of union ``a`` outside union ``b``."""
    total = length(a)
    for s, e in a:
        lo = np.maximum(b[:, 0], s) if len(b) else np.zeros(0)
        hi = np.minimum(b[:, 1], e) if len(b) else np.zeros(0)
        total -= float(np.sum(np.clip(hi - lo, 0.0, None)))
    return total


def _clip(ev: list, t0: float, t1: float):
    names, iv = [], []
    for name, s, d in ev:
        lo, hi = max(s, t0), min(s + d, t1)
        if hi > lo:
            names.append(name)
            iv.append((lo, hi))
    return names, np.asarray(iv, dtype=np.float64).reshape(-1, 2)


def _label(host: list, mid: float) -> str:
    best, best_d = "host", None
    for name, s, d in host:
        if s <= mid <= s + d and (best_d is None or d < best_d):
            best, best_d = name[len(SPAN_PREFIX):], d
    return best


def reduce(ev: dict, t0_ns: float, t1_ns: float, top: int = 10) -> dict:
    ends = [max(s + d for _, s, d in evs)
            for evs in ev["devices"].values() if evs]
    t1_ns = min([t1_ns] + ends)
    window = (t1_ns - t0_ns) * 1e-9
    per_dev = []
    op_time: dict[str, float] = {}
    gaps = []
    for dev in sorted(ev["devices"]):
        names, iv = _clip(ev["devices"][dev], t0_ns, t1_ns)
        coll = np.asarray([is_collective(n) for n in names], bool)
        every = union(iv)
        comp = union(iv[~coll]) if len(iv) else iv
        colls = union(iv[coll]) if len(iv) else iv
        per_dev.append({
            "busy_s": length(every) * 1e-9,
            "compute_s": length(comp) * 1e-9,
            "collective_s": float(np.sum(iv[coll, 1] - iv[coll, 0])) * 1e-9
            if len(iv) else 0.0,
            "collective_alone_s": minus(colls, comp) * 1e-9,
        })
        for n, (s, e) in zip(names, iv):
            op_time[n] = op_time.get(n, 0.0) + (e - s) * 1e-9
        edges = np.concatenate([[t0_ns], comp.reshape(-1), [t1_ns]])
        for g0, g1 in edges.reshape(-1, 2):
            if g1 > g0:
                gaps.append(((g1 - g0) * 1e-9,
                             _label(ev["host"], 0.5 * (g0 + g1))))
    n = max(len(per_dev), 1)
    mean = {k: sum(d[k] for d in per_dev) / n
            for k in ("busy_s", "compute_s", "collective_s",
                      "collective_alone_s")}
    ops = sorted(((k, v / n) for k, v in op_time.items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps, key=lambda g: -g[0])[:top]
    return dict(mean, window_s=window, devices=len(per_dev),
                device_ops=[[k, v] for k, v in ops],
                idle_gaps=[[label, s] for s, label in gaps],
                **_top_module(ev.get("modules", {}), t0_ns, t1_ns))


def _top_module(modules: dict, t0: float, t1: float) -> dict:
    """The program that ran longest in the window (summed over devices)
    and its mean executions per device that ended inside the window."""
    time_of: dict[str, float] = {}
    runs: dict[str, int] = {}
    for evs in modules.values():
        for name, s, d in evs:
            if s >= t0 and s + d <= t1:
                time_of[name] = time_of.get(name, 0.0) + d
                runs[name] = runs.get(name, 0) + 1
    if not time_of:
        return {"top_module": None, "top_module_runs": 0.0}
    name = max(time_of, key=time_of.get)
    return {"top_module": name,
            "top_module_runs": runs[name] / max(len(modules), 1)}
