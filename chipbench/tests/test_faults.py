"""A run with the timed path broken underneath comes out not correct.
Each test drives the rest of a run (at a small size, on the CPU, with
the chip check skipped) with one fault planted in the program:

* a step or scorer that leaves its state unchanged,
* half of the batch left out,
* the exchange between chips left out (four-chip cell, on two virtual
  CPU devices, in a process of its own),
* an answer altered where it is produced.
"""
import dataclasses
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

from chipbench.tests.conftest import ROOT


def _unchanged(step, gs, st):
    return gs, st


def _half_batch(step, gs, st):
    gs2, st2 = step(gs, st)
    h = st.Z.shape[0] // 2
    return gs2, dataclasses.replace(st2, Z=st2.Z.at[h:].set(st.Z[h:]))


def _altered(step, gs, st):
    gs2, st2 = step(gs, st)
    return dataclasses.replace(gs2, A=gs2.A.at[0, 0].add(1e-3)), st2


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered],
                         ids=["unchanged", "half_batch", "altered"])
def test_fit_fault_is_not_correct(small_cell, monkeypatch, fault):
    import repro.runtime as rt

    from chipbench.harness import fit

    base = rt.MCMCDriver

    class Faulty(base):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            step = self.sampler.step
            self.sampler.step = lambda gs, st: fault(step, gs, st)

    monkeypatch.setattr(rt, "MCMCDriver", Faulty)
    result, checks, _ = fit.run(small_cell("paper-fit"), 7, 0.5, False,
                             time.perf_counter())
    assert result["correct"] is False, checks


def _serve_unchanged(fn, X, m, k):
    return fn(jnp.zeros_like(jnp.asarray(X)), m, k)


def _serve_half(fn, X, m, k):
    X = jnp.asarray(X)
    h = X.shape[0] // 2
    return fn(X.at[h:].set(0.0), m, k)


def _serve_altered(fn, X, m, k):
    return fn(X, m, k) + 1e-2


@pytest.mark.parametrize("fault", [_serve_unchanged, _serve_half,
                                   _serve_altered],
                         ids=["unchanged", "half_batch", "altered"])
def test_serve_fault_is_not_correct(small_cell, monkeypatch, fault):
    from chipbench.harness import serve
    from repro.launch import serve_ibp

    make_op = serve_ibp.make_op

    def faulty(bank, op, n_sweeps):
        fn = make_op(bank, op, n_sweeps)
        return lambda X, m, k: fault(fn, X, m, k)

    monkeypatch.setattr(serve_ibp, "make_op", faulty)
    result, checks, _ = serve.run(small_cell("paper-serve-batch"), 7, 0.5,
                               False, time.perf_counter())
    assert result["correct"] is False, checks


EXCHANGE = r"""
import sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax
from chipbench.harness import bench, fit
bench.setup_jax = lambda config: jax
bench.require_chips = lambda jax, chips: None
from chipbench.tests.conftest import load_cell
cell = load_cell("dp4-fit")
cell.config["data"]["N"] = 200
cell.config["sampler"]["P"] = 2
if {fault}:
    jax.lax.psum = lambda x, axes: x
result, checks, _ = fit.run(cell, 5, 0.5, False, time.perf_counter())
print("CORRECT", result["correct"], checks)
"""


@pytest.mark.parametrize("fault", [False, True], ids=["sound", "no_exchange"])
def test_exchange_left_out_is_not_correct(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    code = EXCHANGE.format(root=ROOT, src=os.path.join(ROOT, "src"),
                           fault=fault)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("CORRECT")]
    if not fault:
        assert p.returncode == 0, p.stderr[-3000:]
        assert line and line[0].startswith("CORRECT True"), line
    else:
        # without the all-reduces each chip draws its own A from a
        # quarter of the data: the run either fails its comparison or
        # the chain runs out of feature slots and the run stops
        assert p.returncode != 0 or line[0].startswith("CORRECT False"), \
            line
