"""The hybrid step's named scopes and the driver's cadence spans
(DESIGN.md §16).

A scope is HLO metadata: the compiled step carries ``ibp_sweep``,
``ibp_tail`` and ``ibp_sync`` in the ``op_name`` of its instructions, so
a profiler trace can split the step's device time by layer. The driver
opens one ``TraceAnnotation`` per cadence phase, which a trace taken
under ``jax.profiler.trace`` records on the host plane.
"""
import collections
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.core.ibp import IBPHypers, SamplerSpec, build_sampler
from repro.data import cambridge_data
from repro.runtime import MCMCDriver

SCOPES = ("ibp_sweep", "ibp_tail", "ibp_sync")
# the paper's instance (cambridge-paper): 900 training rows over P=5
PAPER = dict(P=5, L=5, K_max=32, K_tail=8, K_init=4)


def op_names(hlo: str, opcode: str | None = None) -> list[str]:
    """The ``op_name`` of every instruction (of ``opcode``) in HLO text."""
    op = r"[\w-]+" if opcode is None else re.escape(opcode)
    return re.findall(r"= [^\n]*? " + op + r"\([^\n]*?op_name=\"([^\"]*)\"",
                      hlo)


def scope_of(path: str) -> str | None:
    """The innermost ``ibp_*`` scope in an ``op_name`` path."""
    found = [c for c in path.split("/") if c.startswith("ibp_")]
    return found[-1] if found else None


@pytest.fixture(scope="module")
def paper_step_hlo():
    X = np.random.default_rng(0).normal(size=(900, 36)).astype(np.float32)
    s = build_sampler(SamplerSpec(**PAPER), IBPHypers(), X)
    gs, st = s.init(jax.random.key(0))
    return s._fns.step.lower(s._Xn, gs, st).compile().as_text()


def test_paper_step_carries_the_three_scopes(paper_step_hlo):
    counts = collections.Counter(scope_of(p)
                                 for p in op_names(paper_step_hlo))
    for scope in SCOPES:
        assert counts[scope] > 0, (scope, counts)


def test_paper_step_loops_sit_under_their_scopes(paper_step_hlo):
    whiles = op_names(paper_step_hlo, "while")
    # the tail's serial row scan
    scans = [p for p in whiles if p.endswith("jit(_packed_scan)/while")]
    assert scans and all(scope_of(p) == "ibp_tail" for p in scans), scans
    # the sweep's scan over features
    sweeps = [p for p in whiles
              if p.endswith("jit(_uncollapsed_sweep_jnp)/while")]
    assert sweeps and all(scope_of(p) == "ibp_sweep" for p in sweeps)
    # the master's Beta / Gamma rejection loops
    gammas = [p for p in whiles if "jit(_gamma)" in p]
    assert gammas and all(scope_of(p) == "ibp_sync" for p in gammas)
    # the L sub-iteration loop lies above the scopes and carries none
    assert "jit(step_one)/vmap()/while" in whiles


def test_driver_run_leaves_cadence_spans(tmp_path):
    X, _, _ = cambridge_data(N=40, sigma_n=0.4, seed=5)
    spec = SamplerSpec(P=2, K_max=8, K_tail=4, K_init=2, L=1, n_iters=4,
                       eval_every=2, ckpt_every=2, overflow_every=1,
                       harvest_every=1, harvest_burn=0.0,
                       ckpt_dir=str(tmp_path / "ckpt"))
    drv = MCMCDriver(X, spec, IBPHypers(), X_eval=X[:8])
    drv.run(n_iters=1)                  # compile outside the trace
    drv.spec = drv.cfg = spec.replace(ckpt_dir=str(tmp_path / "fresh"))
    logdir = str(tmp_path / "trace")
    with jax.profiler.trace(logdir):
        drv.run()
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    spans = collections.Counter(
        e.name
        for plane in jax.profiler.ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith("ibp:"))
    # 4 iterations: harvest and poll every one, eval and checkpoint (and
    # so the canonical layout) at iterations 2 and 4
    assert spans == {"ibp:harvest": 4, "ibp:poll": 4, "ibp:canonical": 2,
                     "ibp:eval": 2, "ibp:ckpt": 2}
