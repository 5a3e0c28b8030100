"""Traffic of kind ``fit``: the sampler run as a user runs it.

Set-up generates the configuration's data from the seed, builds one
``MCMCDriver`` and drives it for two iterations with every cadence at 1
(evaluation, harvest, overflow poll, checkpoint), so that every program
the window will run is compiled. The window is that same MCMCDriver's
``run`` with the traffic's cadences, resumed from the set-up's
checkpoint; it starts at its first step and stops at the first step
due after ``seconds``, once the last dispatched iteration is done.
Every step's state in and out is kept; after the window a sample of
them, drawn from the seed, is replayed by the reference
(``reference/compare.py``).
"""
from __future__ import annotations

import contextlib
import tempfile
import time

import numpy as np

from . import bench, cambridge, trace, work


class _WindowOver(Exception):
    pass


def _state(jax, sampler, gs, st) -> dict:
    ss = sampler.to_canonical(st)
    return {
        "Z": np.asarray(ss.Z), "A": np.asarray(gs.A),
        "pi": np.asarray(gs.pi), "active": np.asarray(gs.active),
        "alpha": np.asarray(gs.alpha), "sigma_x": np.asarray(gs.sigma_x),
        "sigma_a": np.asarray(gs.sigma_a), "key": gs.key,
        "key_data": np.asarray(jax.random.key_data(gs.key)),
        "p_prime": int(gs.p_prime), "it": int(gs.it),
    }


def setup(jax, cell, seed: int, ckpt_dir: str):
    """The cell's data from the seed and its MCMCDriver: (it, X_shards
    as the reference reads them (P, N_p, D), N)."""
    from repro.core.ibp import IBPHypers, SamplerSpec
    from repro.runtime import MCMCDriver

    cfg, tr = cell.config, cell.traffic
    d, smp = cfg["data"], cfg["sampler"]
    X, _ = cambridge.cambridge(d["N"], d["sigma_n"], seed)
    X_train, X_eval = cambridge.train_eval_split(X, d["eval_frac"], seed)
    P = smp["P"]
    N = (X_train.shape[0] // P) * P
    Xs = X_train[:N].reshape(P, N // P, -1).astype(np.float64)
    spec = SamplerSpec(
        **smp, n_iters=10 ** 9, eval_every=tr["eval_every"],
        ckpt_every=tr["ckpt_every"], overflow_every=tr["overflow_every"],
        harvest_every=tr["harvest_every"], harvest_burn=tr["harvest_burn"],
        ckpt_dir=ckpt_dir, seed=seed)
    drv = MCMCDriver(X_train, spec, IBPHypers(**cfg["hypers"]),
                     X_eval=X_eval)
    return drv, Xs, N


def run(cell, seed: int, seconds: float, traced: bool, t_start: float):
    jax = bench.setup_jax(cell.config)
    bench.require_chips(jax, cell.chips)
    cfg, tr = cell.config, cell.traffic
    smp = cfg["sampler"]
    P, L = smp["P"], smp["L"]
    tmp = tempfile.TemporaryDirectory(prefix="chipbench-fit-")
    drv, Xs, N = setup(jax, cell, seed, tmp.name)
    spec = drv.spec
    sampler = drv.sampler
    span = jax.profiler.TraceAnnotation

    def spanned(fn, name):
        def call(*a, **k):
            with span(trace.SPAN_PREFIX + name):
                return fn(*a, **k)
        return call

    drv.evaluate = spanned(drv.evaluate, "eval")
    drv.save_bank = spanned(drv.save_bank, "bank_save")
    drv.bank_builder.add_state = spanned(drv.bank_builder.add_state,
                                         "harvest")

    win = {"armed": False, "t0": None, "stop": None, "steps": []}
    compiles = bench.compile_log(jax)
    step = sampler.step

    def timed_step(gs, st):
        if win["armed"]:
            now = time.perf_counter()
            if win["t0"] is None:
                win["t0"], win["stop"] = now, now + seconds
                win["compiles0"] = len(compiles)
            elif now >= win["stop"]:
                raise _WindowOver
        with span(trace.SPAN_PREFIX + "step"):
            out = step(gs, st)
        if win["armed"]:
            win["steps"].append((gs, st, out))
        return out

    sampler.step = timed_step

    # set-up: every cadence at 1 for two iterations compiles what the
    # window runs; the window resumes from the checkpoint this writes
    drv.spec = drv.cfg = spec.replace(eval_every=1, ckpt_every=2,
                                      overflow_every=1, harvest_every=1)
    drv.run(n_iters=2)
    drv.spec = drv.cfg = spec

    logdir = None
    if traced:
        logdir = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
        trace.start(logdir.name)
    win["armed"] = True
    with contextlib.suppress(_WindowOver):
        drv.run(n_iters=10 ** 9)
    with span(trace.SPAN_PREFIX + "drain"):
        jax.block_until_ready(win["steps"][-1][2])
    t_end = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    info = {"window_compiles": len(compiles) - win["compiles0"]}
    n_iter = len(win["steps"])
    window_s = t_end - win["t0"]
    setup_s = win["t0"] - t_start
    device = bench.device_info(jax)

    # correctness: a sample of the window's iterations, replayed
    rng = np.random.default_rng(seed)
    picks = sorted(rng.choice(n_iter, size=min(tr["check_iterations"],
                                               n_iter), replace=False))
    pairs = [(_state(jax, sampler, gs, st),
              _state(jax, sampler, out[0], out[1]))
             for gs, st, out in (win["steps"][i] for i in picks)]
    win["steps"].clear()
    del drv, sampler
    tmp.cleanup()
    from chipbench.reference import compare

    numbers = {"z_off": 0.0, "master_gap": 0.0}
    for s_in, s_out in pairs:
        got = compare.fit_numbers(Xs, s_in, s_out, cfg["hypers"], L,
                                  smp["K_tail"], P)
        numbers = {k: max(numbers[k], got[k]) for k in numbers}
    correct, checks = bench.checks_line(numbers, cell.limits)

    result = {"correct": correct, "attempted": n_iter, "failed": 0}
    if not traced:
        result["metrics"] = {
            "fit_iter_per_s": {"value": n_iter / window_s, "unit": "iter/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        result["device"] = device
        return result, checks, info

    ev = trace.events(logdir.name)
    logdir.cleanup()
    t0 = min(h[1] for h in ev["host"] if h[0] == trace.SPAN_PREFIX + "step")
    t1 = max(h[1] + h[2] for h in ev["host"]
             if h[0] == trace.SPAN_PREFIX + "drain")
    red = trace.reduce(ev, t0, t1)
    ctx = {
        "trace": red, "chips": cell.chips,
        "peak": bench.peak_of(device["kind"]),
        "flops_per_unit": work.hybrid_iteration_flops(
            N, Xs.shape[-1], smp["K_max"], smp["K_tail"], L, P),
    }
    result["metrics"] = bench.read_metrics(cell, ctx)
    result["device"] = dict(device, busy_s=red["busy_s"],
                            window_s=red["window_s"])
    result["breakdown"] = {"device_ops": red["device_ops"],
                           "idle_gaps": red["idle_gaps"]}
    info.update(traced_runs=red["top_module_runs"], program=red["top_module"])
    return result, checks, info

