"""The four-chip cell ``dp4-fit``, shrunk to N 400 with its P 4 kept, on
four virtual CPU devices in a process of its own: run through the fit
runner it comes out correct against the float64 reference, and with the
all-reduces of the master sync left out it does not.
"""
import os
import subprocess
import sys

import pytest

from chipbench.tests.conftest import ROOT

RUN = r"""
import sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax
from chipbench.harness import bench, fit
bench.setup_jax = lambda config: jax
bench.require_chips = lambda jax, chips: None
from chipbench.tests.conftest import load_cell
cell = load_cell("dp4-fit")
cell.config["data"]["N"] = 400
assert cell.config["sampler"]["P"] == 4 == jax.device_count()
assert cell.config["sampler"]["data"] == "shardmap"
if {fault}:
    jax.lax.psum = lambda x, axes: x
result, checks, info = fit.run(cell, 2100000017, 0.5, False,
                               time.perf_counter())
print("CORRECT", result["correct"], result["attempted"], checks, info)
"""


@pytest.mark.parametrize("fault", [False, True], ids=["sound", "no_exchange"])
def test_dp4_cell_on_four_devices(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = RUN.format(root=ROOT, src=os.path.join(ROOT, "src"), fault=fault)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("CORRECT")]
    if not fault:
        assert p.returncode == 0, p.stderr[-3000:]
        assert line and line[0].startswith("CORRECT True"), line
        assert "'window_compiles': 0" in line[0], line
    elif p.returncode:
        # each chip then draws the master's A from its own quarter of
        # the data: the chain may run out of feature slots and stop...
        assert "overflow at it=" in p.stderr, p.stderr[-3000:]
    else:
        # ...or it runs on, and the comparison fails
        assert line and line[0].startswith("CORRECT False"), line
