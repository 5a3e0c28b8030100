"""Posterior-predictive serving loop over a harvested ``SampleBank``.

The inference counterpart of ``launch/mcmc.py`` (DESIGN.md §15): load a
bank harvested with ``--harvest-every``, then run a microbatching
request loop —

    queue → pad-to-bucket → one jitted (S × B)-batched score → respond

Requests of ragged sizes are coalesced up to ``--batch`` rows, padded to
a power-of-two row bucket (8, 16, ..., batch) so the jit cache stays
O(log batch), scored in ONE dispatch across the whole ensemble, and
answered per-request. Throughput (rows/s) and latency percentiles —
each coalesced request is charged its microbatch's FULL dispatch wall
time; queueing delay before the dispatch is not modeled — are reported
and merged into the repo-root ``BENCH_<date>.json`` under the
``"serving_loop"`` key.

Usage:
  # fit + harvest, then serve the bank
  python -m repro.launch.mcmc --N 500 --iters 400 --harvest-every 10 \\
      --ckpt-dir artifacts/ckpt/mcmc
  python -m repro.launch.serve_ibp --bank artifacts/ckpt/mcmc/bank.npz \\
      --op loglik --requests 64

Knobs:

  --bank PATH          SampleBank npz (from --harvest-every / save_bank)
  --op loglik|anomaly|encode|impute
                       which predictive op the loop serves
  --batch INT          microbatch row budget per dispatch (default 256)
  --requests INT       synthetic requests to generate (smoke/bench mode)
  --max-request INT    max rows per synthetic request
  --missing FLOAT      missing-dim fraction for --op impute masks
  --n-sweeps INT       Gibbs sweeps per sample inside the scorer
  --seed INT           request-stream seed
  --bench-json PATH    merge the serving section here (default "none" —
                       ordinary serving runs leave the tracked perf
                       trajectory untouched; "" = repo-root
                       BENCH_<date>.json to record a trajectory point)
  --smoke              tiny sizes + sanity assertions (CI fast gate)
"""
from __future__ import annotations

import argparse
import datetime
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ibp import predict
from repro.core.ibp import math as ibm
from repro.launch.jax_cache import REPO_ROOT, enable_compile_cache

OPS = ("loglik", "anomaly", "encode", "impute")


def row_buckets(batch: int) -> tuple[int, ...]:
    """Power-of-two row-count ladder 8, 16, ..., batch — the §14 bucket
    ladder applied to the batch row axis (one policy, one helper)."""
    return ibm.live_buckets(batch)


def pad_to_bucket(X: np.ndarray, buckets: tuple[int, ...]) -> np.ndarray:
    """Zero-pad rows up to the smallest bucket that fits (zero rows are
    scored too — callers slice the first len(X) results)."""
    n = X.shape[0]
    B = ibm.pick_bucket(buckets, n, 0)
    if B == n:
        return X
    return np.concatenate([X, np.zeros((B - n, X.shape[1]), X.dtype)])


def make_op(bank, op: str, n_sweeps: int):
    """The jitted scorer for one op: fn(X_padded, mask, key) -> host array.

    Every op is one (S samples × B rows)-batched dispatch; per-request
    results are sliced on the host after the fetch. Only ``impute``
    consumes the request masks — the other ops treat serving rows as
    fully observed and pass ``mask=None`` so they run predict's unmasked
    fast path (the trace-time branch §15 optimizes; the perf gate in
    benchmarks/run.py times exactly this path)."""
    if op == "loglik":
        return lambda X, m, k: predict.predictive_loglik(
            bank, X, k, n_sweeps=n_sweeps)
    if op == "anomaly":
        return lambda X, m, k: predict.anomaly_score(
            bank, X, k, n_sweeps=n_sweeps)
    if op == "encode":
        return lambda X, m, k: predict.encode(
            bank, X, k, n_sweeps=n_sweeps)
    if op == "impute":
        return lambda X, m, k: predict.impute(
            bank, X, m, k, n_sweeps=n_sweeps)
    raise ValueError(f"op={op!r} not in {OPS}")


def synth_requests(n_requests: int, max_rows: int, D: int, seed: int,
                   missing: float):
    """Synthetic request stream: Cambridge held-out-like rows in ragged
    request sizes, with a per-request observation mask."""
    from repro.data import cambridge_data

    rng = np.random.default_rng(seed)
    N = max(n_requests * max_rows, 64)
    X, _, _ = cambridge_data(N=N, sigma_n=0.5, seed=seed + 1)
    if X.shape[1] != D:
        # bank trained on different D (synthetic bench banks): plain noise
        X = rng.normal(size=(N, D)).astype(np.float32)
    reqs, at = [], 0
    for _ in range(n_requests):
        n = int(rng.integers(1, max_rows + 1))
        rows = X[at:at + n]
        at += n
        mask = (rng.random(rows.shape) >= missing).astype(np.float32)
        mask[mask.sum(axis=1) < 1.0, 0] = 1.0  # at least one observed dim
        reqs.append((rows.astype(np.float32), mask))
    return reqs


def serve(bank, reqs, op: str, batch: int, n_sweeps: int, seed: int):
    """The microbatching loop. Returns (responses, stats dict)."""
    buckets = row_buckets(batch)
    fn = make_op(bank, op, n_sweeps)
    key = jax.random.key(seed)

    # warm the jit cache at every bucket so steady-state latency is
    # measured, not compilation (serving contract: compile at startup)
    D = bank.D
    t0 = time.time()
    for B in buckets:
        z = jnp.zeros((B, D), jnp.float32)
        jax.block_until_ready(fn(z, jnp.ones_like(z), key))
    t_warm = time.time() - t0

    # oversized requests are split into <= batch fragments up front; the
    # fragments keep their request index so the per-request response is
    # reassembled at the end — one response per request, always, and the
    # caller's ``reqs`` list is never mutated
    frags = []
    for ri, (rows, mask) in enumerate(reqs):
        for at in range(0, rows.shape[0], batch):
            frags.append((ri, rows[at:at + batch], mask[at:at + batch]))

    parts: dict[int, list] = {ri: [] for ri in range(len(reqs))}
    req_lat_us = [0.0] * len(reqs)
    rows_done = 0
    t0 = time.time()
    i = 0
    while i < len(frags):
        # coalesce queued fragments up to the batch row budget
        take, n_rows = [], 0
        while i < len(frags) and n_rows + frags[i][1].shape[0] <= batch:
            take.append(frags[i])
            n_rows += frags[i][1].shape[0]
            i += 1
        Xb = np.concatenate([r for _, r, _ in take])
        Mb = np.concatenate([m for _, _, m in take])
        t_req = time.time()
        Xp = pad_to_bucket(Xb, buckets)
        Mp = pad_to_bucket(Mb, buckets)
        key, kreq = jax.random.split(key)
        out = np.asarray(jax.block_until_ready(fn(Xp, Mp, kreq)))
        # respond: slice the batched result back per fragment
        out = out[..., :n_rows, :] if op == "encode" else out[:n_rows]
        at = 0
        for ri, rows, _ in take:
            n = rows.shape[0]
            parts[ri].append(out[..., at:at + n, :] if op == "encode"
                             else out[at:at + n])
            at += n
        dt = time.time() - t_req
        # every request in the microbatch waits for the WHOLE dispatch:
        # that full wall time is its latency (coalescing buys throughput,
        # not per-request speed — the percentiles must say so). A request
        # split across several microbatches accumulates EACH of its
        # dispatches' wall time: its fragments run in consecutive
        # batches, so the sum is its true completion latency.
        for ri in {ri for ri, _, _ in take}:
            req_lat_us[ri] += dt * 1e6
        rows_done += n_rows
    t_total = time.time() - t0

    def assemble(p):
        if len(p) == 1:
            return p[0]
        if not p:  # zero-row request: well-shaped empty response
            if op == "encode":
                return np.zeros((bank.S, 0, bank.K), np.float32)
            return np.zeros((0, D) if op == "impute" else (0,), np.float32)
        return np.concatenate(p, axis=-2 if op == "encode" else 0)

    responses = [assemble(parts[ri]) for ri in range(len(reqs))]
    lat = np.asarray(sorted(req_lat_us)) if req_lat_us else np.zeros(1)
    stats = {
        "op": op, "S": bank.S, "K": bank.K, "D": bank.D,
        "batch": batch, "n_sweeps": n_sweeps,
        "requests": len(reqs), "rows": rows_done,
        "rows_per_s": rows_done / max(t_total, 1e-9),
        "latency_p50_us": float(lat[len(lat) // 2]),
        "latency_p95_us": float(lat[min(len(lat) - 1,
                                        int(0.95 * len(lat)))]),
        "warmup_s": t_warm,
    }
    return responses, stats


def check_responses(reqs, responses, op: str) -> None:
    """One finite response per request, with the request's row count."""
    if len(responses) != len(reqs):
        raise AssertionError(f"{len(reqs) - len(responses)} responses lost")
    for (rows, _), resp in zip(reqs, responses):
        n = rows.shape[0]
        got = resp.shape[-2] if op == "encode" else resp.shape[0]
        if got != n:
            raise AssertionError(f"response rows {got} != request rows {n}")
        if not np.all(np.isfinite(np.asarray(resp))):
            raise AssertionError(f"non-finite {op} scores")


def merge_bench_json(stats: dict, path: str) -> str:
    """Append the serving stats into BENCH_<date>.json via the shared
    tolerant atomic merge (``checkpoint.update_json`` — the same
    two-writer contract ``benchmarks/run.py`` uses)."""
    from repro.checkpoint import update_json

    if not path:
        path = os.path.join(
            REPO_ROOT, f"BENCH_{datetime.date.today().isoformat()}.json")

    def add(payload: dict) -> dict:
        payload.setdefault("serving_loop", []).append(stats)
        return payload

    return update_json(path, add)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--bank", required=True,
                    help="SampleBank npz (launch.mcmc --harvest-every)")
    ap.add_argument("--op", default="loglik", choices=OPS)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-request", type=int, default=48)
    ap.add_argument("--missing", type=float, default=0.25)
    ap.add_argument("--n-sweeps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--bench-json", default="none",
                    help='where to merge the serving_loop stats: "none" '
                         '(default — ordinary serving runs must not '
                         'mutate the tracked perf trajectory), "" = '
                         'repo-root BENCH_<date>.json (recording a '
                         'trajectory point), or an explicit path')
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + sanity assertions (CI fast gate)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.smoke:
        args.requests = min(args.requests, 8)
        args.max_request = min(args.max_request, 12)
        args.batch = min(args.batch, 32)

    bank = predict.SampleBank.load(args.bank)
    print(f"bank: S={bank.S} samples, K={bank.K} features (bucket-"
          f"packed), D={bank.D}, chains={sorted(set(np.asarray(bank.chain).tolist()))}")
    reqs = synth_requests(args.requests, args.max_request, bank.D,
                          args.seed, args.missing if args.op == "impute"
                          else 0.0)
    responses, stats = serve(bank, reqs, args.op, args.batch,
                             args.n_sweeps, args.seed)
    print(f"op={stats['op']}: {stats['rows']} rows / "
          f"{stats['requests']} requests -> "
          f"{stats['rows_per_s']:.0f} rows/s, "
          f"p50={stats['latency_p50_us']:.0f}us "
          f"p95={stats['latency_p95_us']:.0f}us "
          f"(warmup {stats['warmup_s']:.1f}s)")

    if args.smoke:
        check_responses(reqs, responses, args.op)
        print("smoke OK")

    if args.bench_json != "none":
        path = merge_bench_json(stats, args.bench_json)
        print(f"serving section -> {path}")


if __name__ == "__main__":
    main()
