"""Shared forced-host-devices subprocess harness for benchmarks.

Several benchmarks need a REAL multi-device mesh on a CPU-only box
(shardmap / mesh drivers). JAX fixes the device count at backend init,
so the only clean way is a subprocess with
``--xla_force_host_platform_device_count`` in XLA_FLAGS — a pattern that
used to be copy-pasted between benchmarks/collapsed.py and
benchmarks/scaling.py (ROADMAP follow-up). All host devices share one
core, so these runs measure collective/dispatch OVERHEAD, not speedup.

``run_hostdev`` returns raw stdout; ``run_hostdev_json`` extracts a
``BENCH_JSON:{...}`` payload printed by the snippet (None on failure,
with stderr forwarded — benchmarks degrade gracefully, they don't
crash the harness).

These children time XLA's CPU backend. In a parent that holds a TPU they
would report CPU numbers inside a chip run, and a child could not reach
the chip anyway, so ``run_hostdev`` refuses there.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
JSON_TAG = "BENCH_JSON:"


def run_hostdev(code: str, n_devices: int, *, timeout: int = 900,
                check: bool = True) -> subprocess.CompletedProcess:
    """Run ``code`` in a subprocess with ``n_devices`` forced host devices."""
    import jax

    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "run_hostdev times forced CPU host devices in a child process; "
            "this parent holds a TPU, so the numbers would be CPU numbers "
            "in a chip run. Run the multi-device path in this process on "
            "the chip's own devices instead.")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count="
                          f"{n_devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    if check and res.returncode != 0:
        raise RuntimeError(res.stderr[-2000:])
    return res


def run_hostdev_json(code: str, n_devices: int, *,
                     timeout: int = 900) -> dict | None:
    """Run ``code`` and parse the last ``BENCH_JSON:{...}`` stdout line."""
    try:
        res = run_hostdev(code, n_devices, timeout=timeout, check=False)
    except subprocess.TimeoutExpired:
        print("hostdev subprocess timed out", file=sys.stderr)
        return None
    payload = None
    for line in res.stdout.splitlines():
        if line.startswith(JSON_TAG):
            payload = json.loads(line[len(JSON_TAG):])
    if payload is None:
        print(res.stdout[-2000:], res.stderr[-2000:], file=sys.stderr)
    return payload
