"""Smoke run of the hybrid IBP sampler and its serving path on a TPU.

One process runs every phase through the entry points a user calls, at
the paper's configuration (Cambridge data, N=1000, D=36, P=5, L=5,
K_max=32, K_tail=8):

  fit      ``repro.launch.mcmc.main`` with the default knobs (jnp sweep,
           fast collapsed tail), harvesting a SampleBank
  pallas   a short fit with both Pallas kernels in the sampler
  kernels  each of the four Pallas kernels, compiled, against its
           ``ref.py`` oracle at the paper's and a wide width
  carry    one ``fast`` and one ``ref`` collapsed sweep from one state,
           and the carried factor's refresh count
  serve    ``serve_ibp.serve`` for every op over the harvested bank

With ``--four-chips`` it runs only the multi-chip path: the shard_map
layout on four chips against the vmap layout on one, from the same
state, and a few ``--driver mesh`` iterations through the launcher.

The script refuses to run off a TPU, and no phase catches its own
failure: any check that fails raises, the process exits non-zero and
the result line is not printed. Every phase prints its wall time; the
last line of standard output is the JSON result.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # four chips

Outputs (checkpoints, histories, the compile cache) go under
``artifacts/``; each phase deletes its own directory first, because
the launcher resumes from any checkpoint it finds.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "artifacts", "chip_smoke")

# the paper's instance (Doshi-Velez et al. 2009, Cambridge data)
PAPER = dict(N=1000, P=5, L=5, K_max=32, K_tail=8, sigma_n=0.5)
FIT_ITERS, FIT_EVAL, FIT_HARVEST = 60, 20, 5
PALLAS_ITERS, PALLAS_EVAL = 20, 10
# Bands for the last eval record of a fit. The data plant 4 features
# with noise sigma_n = 0.5; a CPU run of the same command ends at
# K+ = 5, sigma_x = 0.502, and the bands leave room for another PRNG
# stream and the chip's float paths, not for a broken sampler.
K_BAND = (3, 8)
SX_BAND = (0.40, 0.65)

# kernel widths: the paper's and the widest the kernels are written for
KERNEL_SIZES = {"paper": (1000, 36, 32), "wide": (8192, 1024, 64)}
# the collapsed carry's boundary budget (tests/test_collapsed_fast.py)
MISMATCH_BUDGET = 2

SERVE_REQUESTS, SERVE_MAX_ROWS, SERVE_BATCH, SERVE_SWEEPS = 16, 48, 256, 3


def say(**kv) -> None:
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def fresh_dir(name: str) -> str:
    path = os.path.join(OUT, name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def require(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# fits through the launcher
# ---------------------------------------------------------------------------


def run_fit(name: str, iters: int, eval_every: int, flags: dict,
            bands: bool) -> tuple[list[dict], str]:
    """One ``mcmc.main`` run of the paper's instance, with ``flags``
    overriding launcher options, from an empty checkpoint directory;
    checks its eval records and returns (history, checkpoint dir)."""
    from repro.launch import mcmc

    d = fresh_dir(name)
    hist_path = os.path.join(d, "history.json")
    opts = {"N": PAPER["N"], "P": PAPER["P"], "L": PAPER["L"],
            "K-max": PAPER["K_max"], "K-tail": PAPER["K_tail"],
            "sigma-n": PAPER["sigma_n"], "iters": iters,
            "eval-every": eval_every, "ckpt-dir": d, "out": hist_path,
            **flags}
    argv = [a for k, v in opts.items() for a in (f"--{k}", str(v))]
    t0 = time.time()
    mcmc.main(argv)
    wall = time.time() - t0
    with open(hist_path) as fh:
        hist = json.load(fh)

    # the first record's clock includes compilation; the rest is steady
    first, last = hist[0], hist[-1]
    steady = (last["t"] - first["t"]) / max(last["it"] - first["it"], 1)
    warmup = first["t"] - first["it"] * steady
    say(phase=name, wall_s=f"{wall:.3f}", warmup_compile_s=f"{warmup:.3f}",
        steady_s_per_iter=f"{steady:.5f}", peak_bytes_in_use=peak_bytes())
    for r in hist:
        say(phase=name, it=r["it"], K=r["K"], sigma_x=r["sigma_x"],
            joint_ll_train=r["joint_ll_train"],
            joint_ll_eval=r.get("joint_ll_eval"))

    for r in hist:
        for key in ("joint_ll_train", "joint_ll_eval"):
            v = r.get(key)
            require(v is not None and math.isfinite(v),
                    f"{name}: {key}={v} at it={r['it']}")
    require(last["it"] == iters, f"{name}: last eval at it={last['it']}")
    if bands:
        require(K_BAND[0] <= last["K"] <= K_BAND[1],
                f"{name}: K+={last['K']} outside {K_BAND}")
        require(SX_BAND[0] <= last["sigma_x"] <= SX_BAND[1],
                f"{name}: sigma_x={last['sigma_x']} outside {SX_BAND}")
    return hist, d


def phase_fit() -> str:
    """The paper's instance with the launcher's default knobs; returns
    the harvested bank's path."""
    _, d = run_fit("fit", FIT_ITERS, FIT_EVAL,
                   {"driver": "vmap", "harvest-every": FIT_HARVEST},
                   bands=True)
    bank = os.path.join(d, "bank.npz")
    require(os.path.exists(bank), f"fit harvested no bank at {bank}")
    return bank


def phase_pallas_fit() -> None:
    run_fit("pallas_fit", PALLAS_ITERS, PALLAS_EVAL,
            {"driver": "vmap", "backend": "pallas",
             "collapsed-backend": "pallas"}, bands=True)


# ---------------------------------------------------------------------------
# kernels against their oracles
# ---------------------------------------------------------------------------


def _dense_inputs(N, D, K, seed):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    Z = jnp.asarray(rng.random((N, K)) < 0.3, jnp.float32)
    A = jnp.asarray(rng.standard_normal((K, D)), jnp.float32)
    act = jnp.asarray(rng.random(K) < 0.8, jnp.float32)
    lpi = jnp.asarray(rng.standard_normal(K), jnp.float32)
    u = jnp.asarray(rng.standard_normal((N, K)) * 2, jnp.float32)
    return X, Z, A, act, lpi, u


def _collapsed_row_inputs(K, D, seed):
    """A consistent (M, H, v, q, mean) carry for one row, as in
    tests/test_kernels.py."""
    rng = np.random.default_rng(seed)
    act = np.ones(K, np.float32)
    Zb = (rng.random((5 * K, K)) < 0.3).astype(np.float32)
    M = np.linalg.inv(Zb.T @ Zb + 0.7 * np.eye(K)).astype(np.float32)
    H = (M @ (Zb.T @ rng.standard_normal((5 * K, D)))).astype(np.float32)
    x = rng.standard_normal(D).astype(np.float32)
    z = (rng.random(K) < 0.4).astype(np.float32)
    v = (M @ z).astype(np.float32)
    u = (rng.standard_normal(K) * 2).astype(np.float32)
    args = [jnp.asarray(a) for a in (M, H, x, z, v, np.float32(z @ v),
                                     (z @ H).astype(np.float32), u,
                                     Zb.sum(0), act)]
    return args + [jnp.float32(8 * K), jnp.float32(0.5)]


def _n_outside(got, want, rtol, atol) -> int:
    got, want = np.asarray(got), np.asarray(want)
    return int(np.sum(np.abs(got - want) > atol + rtol * np.abs(want)))


def phase_kernels() -> None:
    """Each kernel compiled against its oracle, with the tolerances of
    tests/test_kernels.py: gibbs_flip and collapsed_row exactly,
    feature_stats and gaussian_sse to f32 rounding."""
    from repro.kernels.collapsed_row import (collapsed_row_flip_pallas,
                                             collapsed_row_flip_ref)
    from repro.kernels.feature_stats import (feature_stats_core,
                                             feature_stats_ref)
    from repro.kernels.gaussian_sse import gaussian_sse_core, gaussian_sse_ref
    from repro.kernels.gibbs_flip import gibbs_flip_core, gibbs_flip_ref

    t0 = time.time()
    failures = []
    for size, (N, D, K) in KERNEL_SIZES.items():
        X, Z, A, act, lpi, u = _dense_inputs(N, D, K, seed=N + D + K)
        s = jnp.float32(0.5)

        got = gibbs_flip_core(X, Z, A, lpi, act, u, s, interpret=False)
        want = gibbs_flip_ref(X, Z, A, lpi, act, u, s)
        bad = int(jnp.sum(got != want))
        say(phase="kernels", kernel="gibbs_flip", size=size,
            mismatched_bits=bad, of=N * K)
        if bad:
            failures.append(f"gibbs_flip/{size}: {bad} bits")

        ztz, ztx, m = feature_stats_core(X, Z, interpret=False)
        ztz_r, ztx_r, m_r = feature_stats_ref(X, Z)
        bad = (_n_outside(ztz, ztz_r, 1e-5, 1e-5)
               + _n_outside(ztx, ztx_r, 1e-5, 1e-4)
               + _n_outside(m, m_r, 0.0, 0.0))
        say(phase="kernels", kernel="feature_stats", size=size,
            outside_tol=bad, of=K * K + K * D + K,
            max_abs_ztx=float(jnp.max(jnp.abs(ztx - ztx_r))))
        if bad:
            failures.append(f"feature_stats/{size}: {bad} entries")

        got = float(gaussian_sse_core(X, Z, A, act, interpret=False))
        want = float(gaussian_sse_ref(X, Z, A, act))
        rel = abs(got - want) / abs(want)
        say(phase="kernels", kernel="gaussian_sse", size=size,
            rel_err=f"{rel:.3e}", outside_tol=int(rel > 1e-5))
        if rel > 1e-5:
            failures.append(f"gaussian_sse/{size}: rel err {rel:.3e}")

        args = _collapsed_row_inputs(K, D, seed=K + D)
        ref = collapsed_row_flip_ref(*args)
        pal = collapsed_row_flip_pallas(*args, interpret=False)
        names = ("z", "v", "q", "mean")
        bad = {n: _n_outside(p, r, 0.0, 0.0)
               for n, p, r in zip(names, pal, ref)}
        say(phase="kernels", kernel="collapsed_row", size=size,
            **{f"mismatched_{n}": c for n, c in bad.items()},
            max_abs_mean=float(jnp.max(jnp.abs(pal[3] - ref[3]))))
        if any(bad.values()):
            failures.append(f"collapsed_row/{size}: {bad}")
    say(phase="kernels", wall_s=f"{time.time() - t0:.3f}",
        peak_bytes_in_use=peak_bytes())
    require(not failures, "kernels off their oracles: " + "; ".join(failures))


# ---------------------------------------------------------------------------
# the collapsed carry
# ---------------------------------------------------------------------------


def phase_carry() -> None:
    """One fast and one ref collapsed sweep from the same state must stay
    within the boundary budget of tests/test_collapsed_fast.py, and the
    drift monitor must not force many refreshes beyond the cadence."""
    from repro.core.ibp import IBPHypers, collapsed_sweep, init_state
    from repro.core.ibp import math as ibm
    from repro.core.ibp.collapsed import (DEFAULT_REFRESH,
                                          collapsed_row_scan)
    from repro.data import cambridge_data

    t0 = time.time()
    X, _, _ = cambridge_data(N=PAPER["N"], sigma_n=PAPER["sigma_n"], seed=0)
    X = jnp.asarray(X)
    N, D = X.shape
    hyp = IBPHypers()
    st = init_state(jax.random.key(0), N, D, K_max=PAPER["K_max"], K_init=4)
    for _ in range(3):  # leave the cold start before comparing
        st = collapsed_sweep(st, X, hyp, backend="ref")
    a = collapsed_sweep(st, X, hyp, backend="ref")
    b = collapsed_sweep(st, X, hyp, backend="fast")
    bits = int(jnp.sum(a.Z * a.active[None, :] != b.Z * b.active[None, :]))
    sx_rel = abs(float(a.sigma_x) - float(b.sigma_x)) / float(a.sigma_x)
    al_rel = abs(float(a.alpha) - float(b.alpha)) / float(a.alpha)
    k_ref, k_fast = int(a.active.sum()), int(b.active.sum())

    # refresh count of the carried factor over one scan of the same
    # state, in both float paths of the fast backend
    m = jnp.sum(st.Z * st.active[None, :], axis=0)
    ZtZ = (st.Z.T @ st.Z) * ibm.mask_outer(st.active)
    ZtX = (st.Z.T @ X) * st.active[:, None]
    scheduled = N // DEFAULT_REFRESH
    n_refresh = {}
    for pack in (False, True):
        out = collapsed_row_scan(
            st.Z, st.active, ZtZ, ZtX, m, X, jax.random.key(7), st.alpha,
            st.sigma_x, st.sigma_a, N=float(N), backend="fast", pack=pack)
        n_refresh["packed" if pack else "unpacked"] = int(out[5])
    say(phase="carry", K_plus=int(st.active.sum()), mismatched_bits=bits,
        budget=MISMATCH_BUDGET, sigma_x_rel=f"{sx_rel:.3e}",
        alpha_rel=f"{al_rel:.3e}", K_ref=k_ref, K_fast=k_fast,
        n_refresh_unpacked=n_refresh["unpacked"],
        n_refresh_packed=n_refresh["packed"], scheduled_refreshes=scheduled,
        wall_s=f"{time.time() - t0:.3f}", peak_bytes_in_use=peak_bytes())
    require(bits <= MISMATCH_BUDGET,
            f"fast sweep {bits} bits off ref (budget {MISMATCH_BUDGET})")
    require(sx_rel <= 1e-3 and al_rel <= 1e-3 and k_ref == k_fast,
            f"fast sweep hypers off ref: sigma_x {sx_rel:.3e}, alpha "
            f"{al_rel:.3e}, K+ {k_fast} vs {k_ref}")
    # drift refreshes beyond the cadence: a healthy carry adds a few
    for name, n in n_refresh.items():
        require(n <= 2 * scheduled,
                f"{name} carry refreshed {n} times, cadence {scheduled}")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def phase_serve(bank_path: str) -> None:
    from repro.core.ibp.predict import SampleBank
    from repro.launch import serve_ibp

    bank = SampleBank.load(bank_path)
    for op in serve_ibp.OPS:
        t0 = time.time()
        reqs = serve_ibp.synth_requests(
            SERVE_REQUESTS, SERVE_MAX_ROWS, bank.D, seed=0,
            missing=0.25 if op == "impute" else 0.0)
        responses, stats = serve_ibp.serve(bank, reqs, op, SERVE_BATCH,
                                           SERVE_SWEEPS, seed=0)
        serve_ibp.check_responses(reqs, responses, op)
        say(phase="serve", op=op, S=bank.S, K=bank.K,
            requests=stats["requests"], rows=stats["rows"],
            rows_per_s=f"{stats['rows_per_s']:.1f}",
            p50_us=f"{stats['latency_p50_us']:.1f}",
            warmup_compile_s=f"{stats['warmup_s']:.3f}",
            wall_s=f"{time.time() - t0:.3f}",
            peak_bytes_in_use=peak_bytes())


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def phase_four_chips() -> None:
    """shard_map with P=4 over four chips against vmap with P=4 on one,
    from the same canonical state, then a few mesh-driver iterations."""
    from repro.core.ibp import IBPHypers, SamplerSpec, build_sampler
    from repro.data import cambridge_data

    t0 = time.time()
    X, _, _ = cambridge_data(N=PAPER["N"], sigma_n=PAPER["sigma_n"], seed=0)
    hyp = IBPHypers()
    spec = SamplerSpec(P=4, K_max=PAPER["K_max"], K_tail=PAPER["K_tail"],
                       L=PAPER["L"])
    sv = build_sampler(spec, hyp, X)
    sm = build_sampler(spec.replace(data="shardmap"), hyp, X)
    gs_v, st_v = sv.init(jax.random.key(2))
    gs_s, st_s = gs_v, sm.from_canonical(sv.to_canonical(st_v))

    # rows split over the four chips, nothing held on one device alone
    n_rows = sm.N // 4
    for name, arr in (("X", sm._Xn), ("Z", st_s[0])):
        shards = arr.addressable_shards
        devs = {s.device for s in shards}
        rows = sorted(s.data.shape[0] for s in shards)
        say(phase="four_chips", array=name, shards=len(shards),
            devices=len(devs), rows_per_shard=rows)
        require(len(devs) == 4 and rows == [n_rows] * 4,
                f"{name} is not split into 4 x {n_rows} rows: {rows}")
    hlo = sm._fns.step.lower(sm._Xn, gs_s, *st_s).compile().as_text()
    n_ar = hlo.count("all-reduce(")
    say(phase="four_chips", all_reduce_ops=n_ar)
    require(n_ar > 0, "the compiled shard_map step holds no all-reduce")

    first_flip = None
    for step in range(1, 6):
        gs_v, st_v = sv.step(gs_v, st_v)
        gs_s, st_s = sm.step(gs_s, st_s)
        Zv = np.asarray(sv.to_canonical(st_v).Z)
        Zs = np.asarray(sm.to_canonical(st_s).Z)
        bits = int(np.sum(Zv != Zs))
        sx_v, sx_s = float(gs_v.sigma_x), float(gs_s.sigma_x)
        say(phase="four_chips", step=step, mismatched_Z_bits=bits,
            sigma_x_vmap=sx_v, sigma_x_shardmap=sx_s,
            K_vmap=int(gs_v.active.sum()), K_shardmap=int(gs_s.active.sum()))
        if bits and first_flip is None:
            first_flip = step
    say(phase="four_chips", first_step_with_flipped_bits=first_flip)
    require(first_flip is None, f"shardmap left vmap at step {first_flip}")
    np.testing.assert_allclose(float(gs_v.sigma_x), float(gs_s.sigma_x),
                               rtol=1e-5)
    np.testing.assert_allclose(float(gs_v.sigma_a), float(gs_s.sigma_a),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gs_v.A), np.asarray(gs_s.A),
                               atol=1e-5)
    require(int(gs_v.p_prime) == int(gs_s.p_prime), "p' differs")
    say(phase="four_chips", wall_s=f"{time.time() - t0:.3f}",
        peak_bytes_in_use=peak_bytes())

    run_fit("mesh_fit", 10, 5, {"driver": "mesh", "chains": 2, "P": 2},
            bands=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip shard_map/mesh phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX runs on {dev.platform!r}, not on a TPU",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if jax.device_count() < want:
        print(f"chip_smoke: needs {want} chips, JAX sees "
              f"{jax.device_count()}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.jax_cache import enable_compile_cache

    say(device_kind=repr(dev.device_kind), device_count=jax.device_count(),
        jax=jax.__version__, compile_cache=enable_compile_cache())
    t0 = time.time()
    if args.four_chips:
        phase_four_chips()
    else:
        bank = phase_fit()
        phase_pallas_fit()
        phase_kernels()
        phase_carry()
        phase_serve(bank)
    say(total_wall_s=f"{time.time() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
