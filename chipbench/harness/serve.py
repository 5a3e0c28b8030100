"""Traffic of kind ``serve``: a backlog of records scored through
``serve_ibp.serve`` back to back, so the queue never empties.

Set-up draws the bank's samples from the seed and packs them with the
program's ``BankBuilder``, draws the requests (sizes from the traffic's
log-normal, drawn once from its own seed, so every run serves the same
sizes, in an order drawn from the run's seed), and calls ``serve`` once
per op, which compiles every row bucket of that op. The window hands
chunks of ``chunk_requests`` requests to ``serve``, one op per chunk in
the traffic's ``op_cycle`` (its order drawn from the seed), until
``seconds`` have passed; it ends with the last chunk's answers.

Every scorer dispatch of the first cycle's chunks is kept (its padded
rows, mask and key); after the window, ``check_microbatches`` of them
per op, drawn from the seed, are scored by the reference, and every
request that lies in them is compared with what ``serve`` answered.
"""
from __future__ import annotations

import time

import numpy as np

from . import bench, cambridge, trace, work


def bank_samples(cfg: dict, seed: int, K_bank: int) -> dict:
    """S posterior-like samples (float32): the four bars with a little
    noise, up to ``live[1] - 4`` weak spurious features, live features
    in the leading slots of a ``K_bank``-wide layout."""
    b, D = cfg["bank"], cfg["data"]["D"]
    rng = np.random.default_rng([seed, 1])
    S = b["S"]
    A = np.zeros((S, K_bank, D), np.float32)
    pi = np.zeros((S, K_bank), np.float32)
    act = np.zeros((S, K_bank), np.float32)
    bars = cambridge.features()
    for s in range(S):
        k = int(rng.integers(b["live"][0], b["live"][1] + 1))
        A[s, :4] = bars + b["a_noise"] * rng.standard_normal((4, D))
        A[s, 4:k] = b["spurious_scale"] * rng.standard_normal((k - 4, D))
        pi[s, :4] = 0.5 + 0.02 * rng.standard_normal(4)
        pi[s, 4:k] = b["spurious_pi"]
        act[s, :k] = 1.0
    sx = rng.uniform(*b["sigma_x"], size=S).astype(np.float32)
    return {"A": A, "pi": pi, "active": act, "sigma_x": sx}


def requests(tr: dict, D: int, seed: int):
    """(sizes in serving order, rows, masks) of one chunk."""
    ln = tr["size_lognormal"]
    base = np.random.default_rng(ln["seed"])
    sizes = np.clip(np.rint(ln["median"] * np.exp(
        ln["sigma"] * base.standard_normal(tr["chunk_requests"]))),
        ln["min"], ln["max"]).astype(int)
    rng = np.random.default_rng([seed, 2])
    X, _ = cambridge.cambridge(int(sizes.sum()), 0.5, seed)
    mask = (rng.random(X.shape) >= tr["missing"]).astype(np.float32)
    mask[mask.sum(axis=1) < 1.0, 0] = 1.0
    return sizes, X, mask


def build_bank(cell, seed: int):
    """(bank packed by the program's BankBuilder, the reference's copy of
    its samples, their width)."""
    from repro.core.ibp.predict import BankBuilder

    cfg = cell.config
    D, K_max = cfg["data"]["D"], cfg["sampler"]["K_max"]
    K_bank = max(8, 1 << (cfg["bank"]["live"][1] - 1).bit_length())
    smp = bank_samples(cfg, seed, K_bank)
    builder = BankBuilder(K_max)
    pad = np.zeros(K_max - K_bank, np.float32)
    for s in range(cfg["bank"]["S"]):
        A = np.zeros((K_max, D), np.float32)
        A[:K_bank] = smp["A"][s]
        builder.add(A, np.concatenate([smp["pi"][s], pad]),
                    np.concatenate([smp["active"][s], pad]),
                    smp["sigma_x"][s], cfg["bank"]["sigma_a"],
                    cfg["bank"]["alpha"])
    bank = builder.build()
    if bank.K != K_bank:
        raise RuntimeError(f"bank width {bank.K}, expected {K_bank}")
    return bank, smp, K_bank


class Traffic:
    """The cell's requests: chunk ``c`` is the same request sizes in an
    order drawn from (seed, c), over the same rows; ``cycle[c % len]``
    is its op."""

    def __init__(self, cell, seed: int):
        tr = cell.traffic
        self.seed = seed
        self.sizes, self.X, self.mask = requests(
            tr, cell.config["data"]["D"], seed)
        rng = np.random.default_rng([seed, 3])
        self.cycle = [tr["op_cycle"][i]
                      for i in rng.permutation(len(tr["op_cycle"]))]
        self.chunk_seeds = rng.integers(0, 2 ** 31 - 1, size=100000)
        self.rows = int(self.sizes.sum())

    def op(self, c: int) -> str:
        return self.cycle[c % len(self.cycle)]

    def chunk(self, c: int) -> list:
        order = np.random.default_rng([self.seed, 4, c]).permutation(
            len(self.sizes))
        reqs, at = [], 0
        for i in order:
            n = self.sizes[i]
            reqs.append((self.X[at:at + n], self.mask[at:at + n]))
            at += n
        return reqs


def run(cell, seed: int, seconds: float, traced: bool, t_start: float):
    jax = bench.setup_jax(cell.config)
    bench.require_chips(jax, cell.chips)
    from repro.launch import serve_ibp

    cfg, tr = cell.config, cell.traffic
    D = cfg["data"]["D"]
    bank, smp, K_bank = build_bank(cell, seed)
    traffic = Traffic(cell, seed)
    cycle, chunk = traffic.cycle, traffic.chunk

    span = jax.profiler.TraceAnnotation
    kept: dict[int, list] = {}
    now = {"chunk": None, "dispatches": 0}
    make_op = serve_ibp.make_op

    def recording_make_op(bank_, op, n_sweeps):
        fn = make_op(bank_, op, n_sweeps)

        def score(Xp, Mp, key):
            with span(trace.SPAN_PREFIX + "score"):
                out = fn(Xp, Mp, key)
            now["dispatches"] += 1
            # serve's own bucket warm-up passes device zeros; requests
            # arrive as host rows
            if isinstance(Xp, np.ndarray) and now["chunk"] in kept:
                kept[now["chunk"]].append((Xp, Mp, key))
            return out
        return score

    serve_ibp.make_op = recording_make_op
    for op in sorted(set(cycle)):
        serve_ibp.serve(bank, chunk(0)[:1], op, tr["batch"], tr["n_sweeps"],
                        seed)
    for c in range(len(cycle)):
        kept[c] = []

    logdir = None
    if traced:
        import tempfile
        logdir = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
        trace.start(logdir.name)
    answers, ops_done = {}, []
    now["dispatches"] = 0
    compiles = bench.compile_log(jax)
    t0 = time.perf_counter()
    stop = t0 + seconds
    c = 0
    while c == 0 or time.perf_counter() < stop:
        now["chunk"] = c
        op = traffic.op(c)
        reqs = chunk(c)
        with span(trace.SPAN_PREFIX + "serve_chunk"):
            resp, _ = serve_ibp.serve(bank, reqs, op, tr["batch"],
                                      tr["n_sweeps"],
                                      int(traffic.chunk_seeds[c]))
        answers[c] = resp
        ops_done.append(op)
        c += 1
    t_end = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    serve_ibp.make_op = make_op
    info = {"window_compiles": len(compiles), "chunks": c}
    n_chunks = c
    rows_per_chunk = traffic.rows
    setup_s = t0 - t_start
    device = bench.device_info(jax)

    # every answer: one per request, of its row count, finite
    n_req = n_chunks * len(traffic.sizes)
    failed = 0
    for c in range(n_chunks):
        reqs = chunk(c)
        for (rows, _), a in zip(reqs, answers[c]):
            a = np.asarray(a)
            if a.shape[0] != rows.shape[0] or not np.all(np.isfinite(a)):
                failed += 1

    numbers = check(cell, seed, bank_dims=(cfg["bank"]["S"], K_bank, D),
                    samples=smp, kept=kept, answers=answers, chunk=chunk,
                    cycle=cycle, n_chunks=n_chunks)
    correct, checks = bench.checks_line(numbers, cell.limits)
    correct = correct and failed == 0

    result = {"correct": correct, "attempted": n_req, "failed": failed}
    if not traced:
        result["metrics"] = {
            "serve_rows_per_s": {
                "value": n_chunks * rows_per_chunk / (t_end - t0),
                "unit": "rows/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        result["device"] = device
        return result, checks, info

    ev = trace.events(logdir.name)
    logdir.cleanup()
    chunks = [h for h in ev["host"]
              if h[0] == trace.SPAN_PREFIX + "serve_chunk"]
    red = trace.reduce(ev, min(h[1] for h in chunks),
                       max(h[1] + h[2] for h in chunks))
    # the real rows' work, spread over every scorer dispatch of the
    # window (serve's per-call bucket warm-up dispatches included)
    S = cfg["bank"]["S"]
    flops = sum(rows_per_chunk * S * work.score_row_flops(
        K_bank, D, tr["n_sweeps"], op) for op in ops_done)
    ctx = {"trace": red, "flops_per_unit": flops / now["dispatches"],
           "chips": cell.chips, "peak": bench.peak_of(device["kind"])}
    result["metrics"] = bench.read_metrics(cell, ctx)
    result["device"] = dict(device, busy_s=red["busy_s"],
                            window_s=red["window_s"])
    result["breakdown"] = {"device_ops": red["device_ops"],
                           "idle_gaps": red["idle_gaps"]}
    info.update(traced_runs=red["top_module_runs"], program=red["top_module"])
    return result, checks, info


def reference_rows(mode: str, samples: dict, op: str, calls: list, *,
                   S: int, K: int, n_sweeps: int) -> dict:
    """What the reference at ``mode`` answers for every row of the kept
    scorer dispatches ``calls`` [(padded rows, mask, key)], by the row's
    bytes."""
    from chipbench.reference import ibp
    from chipbench.reference.arith import Arith
    from chipbench.reference.variates import score_uniforms

    ar, rows = Arith(mode), {}
    for Xp, Mp, key in calls:
        uu = score_uniforms(key, S=S, n_sweeps=n_sweeps, K=K, B=Xp.shape[0])
        ref = ibp.score(ar, samples, op, Xp, Mp, uu)
        for r in range(Xp.shape[0]):
            rows.setdefault(Xp[r].tobytes(), ref[r])
    return rows


def matched(reqs: list, answers: list, rows: dict) -> list:
    """(answer, row keys) of every request whose rows all lie in
    ``rows``."""
    out = []
    for (x, _), ans in zip(reqs, answers):
        keys = [x[r].tobytes() for r in range(x.shape[0])]
        if keys and all(k in rows for k in keys):
            out.append((ans, keys))
    return out


def check(cell, seed, *, bank_dims, samples, kept, answers, chunk, cycle,
          n_chunks) -> dict:
    """``gap_p50`` and ``off_share`` over the requests that lie in the
    sampled dispatches of one chunk per op of the first cycle."""
    from chipbench.reference import compare

    S, K, _ = bank_dims
    tr = cell.traffic
    rng = np.random.default_rng([seed, 5])
    gaps = []
    for op in sorted(set(cycle)):
        cs = [c for c in range(min(len(cycle), n_chunks)) if cycle[c] == op]
        if not cs:
            continue
        c = int(rng.choice(cs))
        pick = rng.choice(len(kept[c]), size=min(tr["check_microbatches"],
                                                 len(kept[c])), replace=False)
        rows = reference_rows("f64", samples, op,
                              [kept[c][i] for i in sorted(pick)], S=S, K=K,
                              n_sweeps=tr["n_sweeps"])
        gaps += [compare.answer_gap(ans, np.stack([rows[k] for k in keys]))
                 for ans, keys in matched(chunk(c), answers[c], rows)]
    if not gaps:
        return {}
    return compare.serve_numbers(gaps)
