"""Paper Fig. 2: posterior features recovered from the Cambridge data set.

Runs the collapsed sampler and the hybrid sampler (P=5) and compares the
posterior feature images A against the four ground-truth 6x6 base images
via greedy L2 matching. Artifacts: artifacts/fig2_true.npy,
fig2_collapsed.npy, fig2_hybrid.npy (+ ASCII rendering on stdout).
"""
from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ibp import (
    IBPHypers,
    SamplerSpec,
    build_sampler,
    collapsed_sweep,
    init_state,
)
from repro.core.ibp import math as ibm
from repro.core.ibp.diagnostics import match_features
from repro.data import cambridge_data
from repro.data.cambridge import CAMBRIDGE_FEATURES

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")


def posterior_features_collapsed(X, iters, K_max, seed):
    N, D = X.shape
    st = init_state(jax.random.key(seed), N, D, K_max, K_init=1)
    Xj = jnp.asarray(X)
    hyp = IBPHypers()
    for _ in range(iters):
        st = collapsed_sweep(st, Xj, hyp)
    ZtZ = (st.Z.T @ st.Z) * ibm.mask_outer(st.active)
    ZtX = (st.Z.T @ Xj) * st.active[:, None]
    # posterior MEAN of A given the final Z (Fig. 2 shows features, not draws)
    A, _ = ibm.a_posterior(ZtZ, ZtX, st.active, st.sigma_x, st.sigma_a)
    order = jnp.argsort(-jnp.sum(st.Z, axis=0) * st.active)
    return np.asarray(A[order]), int(jnp.sum(st.active))


def posterior_features_hybrid(X, P, iters, L, K_max, seed):
    smp = build_sampler(
        SamplerSpec(P=P, K_max=K_max, K_tail=8, K_init=4, L=L, seed=seed),
        IBPHypers(), X,
    )
    N = smp.N
    gs, ss = smp.init(jax.random.key(seed))
    for _ in range(iters):
        gs, ss = smp.step(gs, ss)
    Z = ss.Z.reshape(N, -1)
    ZtZ = (Z.T @ Z) * ibm.mask_outer(gs.active)
    ZtX = (Z.T @ smp.X) * gs.active[:, None]
    A, _ = ibm.a_posterior(ZtZ, ZtX, gs.active, gs.sigma_x, gs.sigma_a)
    order = jnp.argsort(-jnp.sum(Z, axis=0) * gs.active)
    return np.asarray(A[order]), int(jnp.sum(gs.active))


def ascii_render(A: np.ndarray, label: str, k: int = 4) -> str:
    """Render the top-k features as 6x6 ASCII blocks side by side."""
    rows = [label]
    imgs = [A[i].reshape(6, 6) for i in range(min(k, A.shape[0]))]
    hi = max(float(np.abs(A[:k]).max()), 1e-6)
    for r in range(6):
        line = []
        for im in imgs:
            line.append("".join(
                "#" if im[r, c] > 0.5 * hi else
                "+" if im[r, c] > 0.25 * hi else "."
                for c in range(6)
            ))
        rows.append("  ".join(line))
    return "\n".join(rows)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--N", type=int, default=300)
    ap.add_argument("--iters", type=int, default=120)
    ap.add_argument("--L", type=int, default=5)
    ap.add_argument("--K-max", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    X, _, A_true = cambridge_data(N=args.N, sigma_n=0.5, seed=args.seed)

    A_c, K_c = posterior_features_collapsed(X, args.iters, args.K_max,
                                            args.seed)
    A_h, K_h = posterior_features_hybrid(X, 5, args.iters, args.L, args.K_max,
                                         args.seed)

    _, sse_c = match_features(A_c[:max(K_c, 4)], A_true)
    _, sse_h = match_features(A_h[:max(K_h, 4)], A_true)

    os.makedirs(ART, exist_ok=True)
    np.save(os.path.join(ART, "fig2_true.npy"), A_true)
    np.save(os.path.join(ART, "fig2_collapsed.npy"), A_c)
    np.save(os.path.join(ART, "fig2_hybrid.npy"), A_h)

    print(ascii_render(A_true, "true features:"))
    print(ascii_render(A_c, f"collapsed (K={K_c}, match SSE={sse_c:.2f}):"))
    print(ascii_render(A_h, f"hybrid P=5 (K={K_h}, match SSE={sse_h:.2f}):"))

    lines = [
        f"fig2__collapsed,0,K={K_c};match_sse={sse_c:.2f}",
        f"fig2__hybrid_P5,0,K={K_h};match_sse={sse_h:.2f}",
    ]
    for ln in lines:
        print(ln)
    return lines


if __name__ == "__main__":
    main()
