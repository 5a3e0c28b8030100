"""IBP hybrid-MCMC launcher — the paper's experiment, end to end.

The CLI builds a ``SamplerSpec`` (DESIGN.md §13) and hands it to
``MCMCDriver``; ``--driver`` names a point on the composable
``chains`` x ``data`` parallelism grid.

Usage:
  python -m repro.launch.mcmc --N 1000 --P 5 --iters 1000 --L 5
  python -m repro.launch.mcmc --driver multichain --chains 4   # + R-hat/ESS
  python -m repro.launch.mcmc --driver shardmap --sync fused   # data mesh
  # composed: C chains x P data shards on a 2-D ("chains","data") mesh —
  # on CPU, force C*P host devices first:
  #   XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
  python -m repro.launch.mcmc --driver mesh --chains 2 --P 2

Kernel knobs (all spec-validated; see DESIGN.md §12–§14):

  --backend jnp|pallas            uncollapsed Z sweep implementation
  --collapsed-backend ref|fast    tail collapsed row step (default fast)
  --chol-refresh INT              fast-path exact-refactor cadence
  --K-tail INT                    in-flight tail features on p'
                                  (must be <= K_max)
  --k-tail-grow INT               adaptive K_tail: max automatic tail
                                  doublings at checkpoint boundaries
                                  when tail saturation accrues
                                  (0 = fixed K_tail; ceiling K_max)
  --sync staged|fused             master-sync collective schedule
  --stale-sync INT                bounded-staleness passes (non-exact)

Posterior-predictive harvest (DESIGN.md §15):

  --harvest-every INT             harvest one posterior sample (per chain)
                                  into the SampleBank every this many
                                  iterations (0 = off)
  --harvest-burn FLOAT            fraction of the run discarded before
                                  harvesting starts (default 0.5)
  --bank-path PATH                bank npz (default <ckpt-dir>/bank.npz);
                                  serve it with repro.launch.serve_ibp
"""
from __future__ import annotations

import argparse
import json
import os

from repro.core.ibp import IBPHypers, SamplerSpec
from repro.core.ibp.api import DRIVERS
from repro.core.ibp.collapsed import DEFAULT_REFRESH
from repro.data import cambridge_data, train_eval_split
from repro.launch.jax_cache import enable_compile_cache
from repro.runtime import MCMCDriver


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--N", type=int, default=1000)
    ap.add_argument("--P", type=int, default=5)
    ap.add_argument("--iters", type=int, default=1000)
    ap.add_argument("--L", type=int, default=5)
    ap.add_argument("--K-max", type=int, default=32)
    ap.add_argument("--K-tail", type=int, default=8,
                    help="in-flight tail features on shard p' (the "
                         "collapsed-birth truncation; <= K_max)")
    ap.add_argument("--k-tail-grow", type=int, default=0,
                    help="adaptive K_tail: maximum automatic tail "
                         "doublings at checkpoint boundaries when the "
                         "tail-saturation counter (eval record "
                         "'tail_sat') accrues; 0 = fixed K_tail, "
                         "ceiling is K_max (DESIGN.md §12)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sigma-n", type=float, default=0.5)
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt/mcmc")
    ap.add_argument("--eval-every", type=int, default=20)
    ap.add_argument("--backend", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--driver", default="vmap", choices=sorted(DRIVERS),
                    help="parallelism layout: vmap (single device), "
                         "multichain (C chains vmapped), shardmap (P-device "
                         "data mesh), mesh (C chains x P data shards on a "
                         "2-D mesh; needs C*P devices)")
    ap.add_argument("--chains", type=int, default=None,
                    help="chain count for --driver multichain/mesh "
                         "(default 4 / 2); values > 1 require a chainful "
                         "driver")
    ap.add_argument("--sync", default="staged", choices=["staged", "fused"],
                    help="master-sync schedule for --driver shardmap/mesh")
    ap.add_argument("--stale-sync", type=int, default=0,
                    help="bounded-staleness passes per iteration (non-exact)")
    ap.add_argument("--collapsed-backend", default="fast",
                    choices=["ref", "fast"],
                    help="tail collapsed row step (default: fast — the "
                         "rank-one Cholesky carry, held to ref by "
                         "tests/test_collapsed_fast.py; on a TPU the whole "
                         "row scan is one fused kernel). ref keeps the "
                         "fresh O(K^3) factorization per row")
    ap.add_argument("--chol-refresh", type=int, default=DEFAULT_REFRESH,
                    help="exact-refactorization cadence of the fast "
                         "collapsed backend (rows between refreshes)")
    ap.add_argument("--harvest-every", type=int, default=0,
                    help="SampleBank harvest cadence in iterations "
                         "(0 = off); chain-batched drivers harvest one "
                         "sample per chain (DESIGN.md §15)")
    ap.add_argument("--harvest-burn", type=float, default=0.5,
                    help="fraction of the run discarded as burn-in "
                         "before harvesting starts")
    ap.add_argument("--bank-path", default="",
                    help="SampleBank npz path (default: "
                         "<ckpt-dir>/bank.npz)")
    ap.add_argument("--out", default="artifacts/mcmc_history.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    X, Ztrue, Atrue = cambridge_data(N=args.N, sigma_n=args.sigma_n,
                                     seed=args.seed)
    X_train, X_eval = train_eval_split(X, eval_frac=0.1, seed=args.seed)

    chains, data = DRIVERS[args.driver]
    # explicit --chains passes through so spec validation can reject it
    # loudly under a chainless driver; the default never does
    default_chains = {"multichain": 4, "mesh": 2}.get(args.driver, 1)
    spec = SamplerSpec(
        P=args.P, K_max=args.K_max, K_tail=args.K_tail,
        k_tail_grow=args.k_tail_grow, L=args.L, n_iters=args.iters,
        eval_every=args.eval_every, ckpt_dir=args.ckpt_dir, seed=args.seed,
        backend=args.backend, chains=chains, data=data,
        n_chains=(args.chains if args.chains is not None else default_chains),
        sync=args.sync, stale_sync=args.stale_sync,
        collapsed_backend=args.collapsed_backend,
        chol_refresh=args.chol_refresh,
        harvest_every=args.harvest_every,
        harvest_burn=args.harvest_burn,
        bank_path=args.bank_path,
    )
    drv = MCMCDriver(X_train, spec, IBPHypers(), X_eval=X_eval)

    def show(r):
        line = (
            f"it={r['it']:5d} t={r['t']:7.1f}s K+={r['K']:4.1f} "
            f"alpha={r['alpha']:.2f} sx={r['sigma_x']:.3f} "
            f"ll_eval={r.get('joint_ll_eval', float('nan')):.1f}"
        )
        if "K_tail" in r:
            line += f" Ktail={r['K_tail']}"
            if r.get("tail_sat", 0):
                line += f" sat={r['tail_sat']}"
        import math
        if "sigma_x_rhat" in r and math.isfinite(r["sigma_x_rhat"]):
            line += (f" rhat(sx)={r['sigma_x_rhat']:.3f}"
                     f" ess(sx)={r['sigma_x_ess']:.0f}")
        print(line, flush=True)

    gs, ss = drv.run(on_eval=show)

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        # early eval records carry NaN diagnostics (not enough draws);
        # bare NaN is not valid JSON — emit null instead
        json.dump(_json_safe(drv.history), fh, indent=1)
    print(f"history -> {args.out}")
    if drv.bank_builder is not None and len(drv.bank_builder):
        # already persisted by the driver's final-iteration checkpoint
        print(f"sample bank ({len(drv.bank_builder)} samples) -> "
              f"{drv.bank_path}")


def _json_safe(obj):
    import math

    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_json_safe(v) for v in obj]
    return obj


if __name__ == "__main__":
    main()
