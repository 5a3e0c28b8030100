"""Pallas-kernel microbenchmarks.

This container is CPU-only, so the kernels execute in interpret mode (Python
per grid cell) — wall time there measures nothing about TPU. What we CAN
measure structurally and report:

  * allclose vs the pure-jnp oracle at a production-ish shape (correctness
    at scale, not just the unit-test shapes);
  * the jnp reference path wall time on CPU (the baseline any TPU time would
    be compared against);
  * per-kernel arithmetic intensity (FLOPs / HBM bytes) at that shape from
    first principles — the quantity the BlockSpec tiling was designed
    around (see kernels/*/kernel.py docstrings).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.collapsed_row import (
    collapsed_row_flip_fast,
    collapsed_row_flip_ref,
)
from repro.kernels.feature_stats import feature_stats, feature_stats_ref
from repro.kernels.gaussian_sse import gaussian_sse, gaussian_sse_ref
from repro.kernels.gibbs_flip import gibbs_flip_core, gibbs_flip_ref


def _time(f, iters=5):
    jax.block_until_ready(f())
    t0 = time.time()
    for _ in range(iters):
        out = f()
    jax.block_until_ready(out)
    return (time.time() - t0) / iters


def _inputs(N, K, D, seed=0):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    Z = jnp.asarray((rng.random((N, K)) < 0.3), jnp.float32)
    A = jnp.asarray(rng.standard_normal((K, D)), jnp.float32)
    act = jnp.ones((K,), jnp.float32)
    return X, Z, A, act, rng


def bench_gibbs_flip(N, K, D, interp_N=128):
    X, Z, A, act, rng = _inputs(N, K, D)
    lpi = jnp.asarray(rng.standard_normal(K), jnp.float32)
    u = jnp.asarray(rng.standard_normal((N, K)) * 2, jnp.float32)
    inv2s2 = jnp.float32(0.5)
    got = gibbs_flip_core(X[:interp_N], Z[:interp_N], A, lpi, act,
                          u[:interp_N], inv2s2)
    want = gibbs_flip_ref(X[:interp_N], Z[:interp_N], A, lpi, act,
                          u[:interp_N], inv2s2)
    assert bool(jnp.all(got == want))
    t_ref = _time(lambda: gibbs_flip_ref(X, Z, A, lpi, act, u, inv2s2))
    # per sweep: K sequential steps, each a rank-1 residual update (2ND) +
    # scoring (3ND); residual stays VMEM-resident -> bytes ~ X + Z(in/out) + A
    flops = 5.0 * N * D * K
    bytes_ = 4.0 * (N * D + 2 * N * K + K * D)
    return t_ref, flops / bytes_


def bench_feature_stats(N, K, D):
    X, Z, _, _, _ = _inputs(N, K, D, seed=1)
    ztz_k, ztx_k, m_k = feature_stats(X[:256], Z[:256])
    ztz_r, ztx_r, m_r = feature_stats_ref(X[:256], Z[:256])
    np.testing.assert_allclose(np.asarray(ztz_k), np.asarray(ztz_r), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ztx_k), np.asarray(ztx_r),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r))
    t_ref = _time(lambda: feature_stats_ref(X, Z))
    # fused: one pass over X and Z produces ZtZ, ZtX, m
    flops = 2.0 * N * K * (K + D)
    bytes_ = 4.0 * (N * D + N * K + K * K + K * D)
    return t_ref, flops / bytes_


def bench_collapsed_row(N, K, D):
    """The K-sequential collapsed bit-flip recurrence, scanned over N rows.

    Correctness: the fast flavor must keep the jnp oracle's decisions
    at this shape. Perf: the ref (full-K, mean-carry) vs
    fast (packed-active, rss/rH-carry) flavors over an N-row scan — the
    "ref-vs-fast" column of the perf trajectory at the recurrence level.
    """
    rng = np.random.default_rng(3)
    Zb = (rng.random((4 * K, K)) < 0.3).astype(np.float32)
    W = (Zb.T @ Zb + 0.7 * np.eye(K)).astype(np.float32)
    M = jnp.asarray(np.linalg.inv(W), jnp.float32)
    H = jnp.asarray(
        np.linalg.solve(W, Zb.T @ rng.standard_normal((4 * K, D))),
        jnp.float32,
    )
    x = jnp.asarray(rng.standard_normal(D), jnp.float32)
    z0 = jnp.asarray((rng.random(K) < 0.3), jnp.float32)
    u = jnp.asarray(rng.standard_normal(K) * 2, jnp.float32)
    mm = jnp.asarray(Zb.sum(0), jnp.float32)
    act = jnp.ones((K,), jnp.float32)
    Nf, i2 = jnp.float32(N), jnp.float32(0.5)

    def start(z):
        v = M @ z
        return v, jnp.dot(z, v), z @ H

    v, q, mean = start(z0)
    base = (M, H, x, z0, v, q, mean, u, mm, act, Nf, i2)
    zr, *_ = collapsed_row_flip_ref(*base)
    zf, *_ = collapsed_row_flip_fast(*base)
    # the packed flavor's float path may round a boundary accept differently
    # (documented; tests budget the same) — don't fail CI on one such bit
    assert int(jnp.sum(zr != zf)) <= 2, "fast diverged from ref beyond budget"

    def scan_with(flip):
        def f(z):
            def body(z, _):
                v, q, mean = start(z)
                z, _, _, _ = flip(M, H, x, z, v, q, mean, u, mm, act, Nf, i2)
                return z, None
            return jax.lax.scan(body, z, jnp.arange(N))[0]
        return jax.jit(f)

    f_ref = scan_with(collapsed_row_flip_ref)
    f_fast = scan_with(collapsed_row_flip_fast)
    t_ref = _time(lambda: f_ref(z0))
    t_fast = _time(lambda: f_fast(z0))
    # per bit: O(K) carry moves + scalar likelihood = ~6K flops; M, H, G
    # stay register/VMEM-resident across the whole K-loop
    flops = 6.0 * N * K * K
    bytes_ = 4.0 * (K * K + K * D + N * K)
    return t_ref, t_fast, flops / bytes_


def bench_gaussian_sse(N, K, D):
    X, Z, A, act, _ = _inputs(N, K, D, seed=2)
    s_k = gaussian_sse(X[:256], Z[:256], A, act)
    s_r = gaussian_sse_ref(X[:256], Z[:256], A, act)
    np.testing.assert_allclose(float(s_k), float(s_r), rtol=1e-4)
    t_ref = _time(lambda: gaussian_sse_ref(X, Z, A, act))
    # fused: residual never hits HBM (ref writes + rereads N*D)
    flops = 2.0 * N * K * D + 3.0 * N * D
    bytes_ = 4.0 * (N * D + N * K + K * D)
    return t_ref, flops / bytes_


def main(argv=None) -> tuple[list[str], list[dict]]:
    """Returns (csv_lines, results).

    ``results`` is the machine-readable form that lands in
    ``BENCH_*.json["kernels"]``: one JSON object per kernel with
    ``name``, ``us`` (jnp-reference wall time), ``allclose``,
    ``arith_intensity`` and a structured ``shape`` — replacing the old
    packed comma-string so the perf trajectory is machine-diffable.
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--N", type=int, default=4096)
    ap.add_argument("--K", type=int, default=64)
    ap.add_argument("--D", type=int, default=256)
    args = ap.parse_args(argv)
    N, K, D = args.N, args.K, args.D

    lines: list[str] = []
    results: list[dict] = []
    for name, fn in [("gibbs_flip", bench_gibbs_flip),
                     ("feature_stats", bench_feature_stats),
                     ("gaussian_sse", bench_gaussian_sse)]:
        t_ref, ai = fn(N, K, D)
        results.append({
            "name": name,
            "us": t_ref * 1e6,
            "allclose": True,
            "arith_intensity": ai,
            "shape": {"N": N, "K": K, "D": D},
        })
        lines.append(
            f"kernel__{name},{t_ref * 1e6:.0f},"
            f"allclose=ok;arith_intensity={ai:.1f};shape=N{N}xK{K}xD{D}"
        )
        print(lines[-1], flush=True)
    # collapsed_row: the row scan is serial, so bench at row-scan scale
    n_rows = min(N, 512)
    t_ref, t_fast, ai = bench_collapsed_row(n_rows, K, min(D, 64))
    results.append({
        "name": "collapsed_row",
        "us": t_ref * 1e6,
        "fast_us": t_fast * 1e6,
        "ref_vs_fast": t_ref / t_fast,
        "allclose": True,
        "arith_intensity": ai,
        "shape": {"N": n_rows, "K": K, "D": min(D, 64)},
    })
    lines.append(
        f"kernel__collapsed_row,{t_ref * 1e6:.0f},"
        f"allclose=ok;fast_us={t_fast * 1e6:.0f};"
        f"ref_vs_fast={t_ref / t_fast:.2f}x;"
        f"arith_intensity={ai:.1f};shape=N{n_rows}xK{K}xD{min(D, 64)}"
    )
    print(lines[-1], flush=True)
    return lines, results


if __name__ == "__main__":
    main()
