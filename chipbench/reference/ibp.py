"""Plain reference of the linear-Gaussian IBP hybrid sampler and of the
bank scorer, written from the model and the paper's algorithm
(Griffiths & Ghahramani 2011; arXiv:1703.03457 Sec. 3), in NumPy.

Both are Monte Carlo algorithms: their answers are functions of the
state and of the random numbers they draw. The reference is handed the
same random numbers (``variates.py`` draws them from the state's key,
in the order the algorithm consumes them), so every Bernoulli decision,
every Gaussian and Gamma draw is the same variable on both sides, and
what is left to compare is the arithmetic.

Hybrid iteration (one chain, P data shards, L sub-iterations):

  every shard p, l = 1..L:
      uncollapsed Gibbs over Z_p given (pi, A), features in slot order;
      on p' only: collapsed Gibbs over the tail features with A* out,
      data R = X_p - Z A, global-N priors, and a Metropolis-Hastings
      birth of j ~ Poisson(alpha/N) new tail features per row
  master sync:
      promote p''s live tail columns into the lowest free slots,
      drop features with no rows, A | Z, X ~ Gaussian, pi_k ~ Beta,
      sigma_x^2, sigma_a^2 ~ inverse Gamma, alpha ~ Gamma, p' ~ Uniform.

Scorer (one sample s of the bank, B rows): a ridge warm start
z0 = 1[(A Aᵀ + sigma² I)⁻¹ A x > 1/2], then ``n_sweeps`` Gibbs sweeps
over z given the observed dimensions; the Rao-Blackwellized
probabilities of the later half of the sweeps, and the joint
log-likelihood of x with the last draw.
"""
from __future__ import annotations

import math

import numpy as np

from .arith import Arith

LOG2PI = math.log(2.0 * math.pi)
J_MAX = 4  # births proposed above this count are refused


def mask_outer(active):
    return active[:, None] * active[None, :]


def padded_W(ZtZ, active, ratio):
    K = ZtZ.shape[0]
    eye = np.eye(K, dtype=ZtZ.dtype)
    return ZtZ * mask_outer(active) + ratio * eye * mask_outer(active) \
        + eye * (1.0 - active)


# ---------------------------------------------------------------------------
# the uncollapsed sweep
# ---------------------------------------------------------------------------


def sweep(ar: Arith, X, Z, A, pi, active, sx, u01):
    """One Gibbs sweep of Z | pi, A, sigma_x over the live slots, in slot
    order, every row at once. ``u01`` (N_p, K): the uniforms."""
    X, Z, A = ar.a(X), ar.a(Z).copy(), ar.a(A)
    R = X - ar.mm(Z, A)
    anorm2 = np.sum(A * A, axis=1)
    lpi = ar.logit(pi, 1e-6)
    u = ar.logit(u01, 1e-6)
    inv2s2 = ar.a(0.5) / ar.a(sx) ** 2
    for k in range(Z.shape[1]):
        a_k, z_k = A[k], Z[:, k]
        R0 = R + z_k[:, None] * a_k[None, :]
        dll = (2.0 * ar.mm(R0, a_k) - anorm2[k]) * inv2s2
        znew = (lpi[k] + dll > u[:, k]).astype(ar.dt) if active[k] > 0 \
            else z_k
        R = R0 - znew[:, None] * a_k[None, :]
        Z[:, k] = znew
    return Z


# ---------------------------------------------------------------------------
# the collapsed tail on p'
# ---------------------------------------------------------------------------


def tail_scan(ar: Arith, Zt, act, R, v, alpha, sx, sa, N):
    """Collapsed Gibbs + MH births over the rows of R, in row order.
    ``v``: per-row variates ``u`` (n, Kt) uniforms, ``jprop`` (n,)
    Poisson proposals, ``uacc`` (n,) acceptance uniforms.
    Returns (Zt, act, m, n_sat)."""
    Zt, act, R = ar.a(Zt).copy(), ar.a(act).copy(), ar.a(R)
    n_rows, D = R.shape
    Kt = Zt.shape[1]
    sx, sa, alpha = ar.a(sx), ar.a(sa), ar.a(alpha)
    m = np.sum(Zt, axis=0)
    ZtZ = ar.mm(Zt.T, Zt)
    ZtX = ar.mm(Zt.T, R)
    ratio = (sx / sa) ** 2
    rho = (sa / sx) ** 2
    inv2s2 = ar.a(0.5) / sx ** 2
    js = np.arange(J_MAX + 1, dtype=ar.dt)
    n_sat = 0
    for n in range(n_rows):
        x, z = R[n], Zt[n].copy()
        m_minus = m - z
        ZtZ_m = ZtZ - np.outer(z, z)
        ZtX_m = ZtX - np.outer(z, x)
        # a row's singletons leave with it and may come back as births
        dead = act * (m_minus <= 0.5)
        z = z * (1.0 - dead * z)
        act_m = act * (1.0 - dead)
        M = ar.chol_inv(padded_W(ZtZ_m, act_m, ratio)) * mask_outer(act_m)
        H = ar.mm(M, ZtX_m * act_m[:, None])
        u = ar.logit(v["u"][n], 1e-7)
        # x | z ~ N(z H, sigma_x^2 (1 + z M zᵀ) I); prior odds m/(N - m)
        for k in range(Kt):
            if not (act_m[k] > 0 and m_minus[k] > 0.5):
                continue
            lls = []
            for bit in (0.0, 1.0):
                z[k] = bit
                q = z @ ar.mm(M, z)
                r = x - ar.mm(z, H)
                s = 1.0 + q
                lls.append(-0.5 * D * np.log(s) - inv2s2 * ar.mm(r, r) / s)
            logodds = (np.log(m_minus[k]) - np.log(ar.a(N) - m_minus[k])
                       + lls[1] - lls[0])
            z[k] = 1.0 if logodds > u[k] else 0.0
        # MH birth: j ~ Poisson(lam), accepted with lik(j) / lik(0)
        q = z @ ar.mm(M, z)
        r = x - ar.mm(z, H)
        s_j = 1.0 + q + js * rho
        ll_j = -0.5 * D * np.log(s_j) - inv2s2 * ar.mm(r, r) / s_j
        free = 1.0 - np.maximum(act_m, z)
        n_free = np.sum(free)
        jp = float(v["jprop"][n])
        acc = np.log(ar.a(v["uacc"][n])) < ll_j[min(int(jp), J_MAX)] - ll_j[0]
        j_new = jp if (acc and jp <= min(J_MAX, n_free)) else 0.0
        n_sat += int(acc and jp <= J_MAX and jp > n_free)
        rank = np.cumsum(free) * free
        born = ((rank >= 1.0) & (rank <= j_new)).astype(ar.dt)
        z = z + born
        act = np.maximum(act_m, born)
        m = m_minus * act_m + z
        ZtZ = ZtZ_m * mask_outer(act_m) + np.outer(z, z)
        ZtX = ZtX_m * act_m[:, None] + np.outer(z, x)
        Zt[n] = z
    return Zt, act, m, n_sat


# ---------------------------------------------------------------------------
# one hybrid iteration
# ---------------------------------------------------------------------------


def promote(Z, Zt, tail_g, active):
    """Tail column j goes to the j-th free slot (by rank among live
    tails); tails beyond the free slots are dropped."""
    K = Z.shape[1]
    free = 1.0 - active
    rank = np.cumsum(tail_g) * tail_g
    kept = tail_g * (rank <= np.sum(free))
    tgt = np.clip(np.searchsorted(np.cumsum(free), np.maximum(rank, 1.0)),
                  0, K - 1)
    Z = Z.copy()
    active = active.copy()
    for j in range(tail_g.shape[0]):
        if kept[j] > 0:
            Z[:, tgt[j]] += Zt[:, j]
            active[tgt[j]] = max(active[tgt[j]], 1.0)
    return Z, active


def iteration_z(ar: Arith, Xs, Z, A, pi, active, alpha, sx, sa, p_prime,
                L, v):
    """Z after one hybrid iteration (P, N_p, K), from the input state and
    the variates; the master's draws do not touch Z."""
    P, N_p, K = Z.shape
    N = P * N_p
    Kt = v["tail"][0]["u"].shape[1]
    Zs = [ar.a(Z[p]) for p in range(P)]
    Zt = np.zeros((N_p, Kt), ar.dt)
    ta = np.zeros((Kt,), ar.dt)
    act = ar.a(active)
    for p in range(P):
        for l in range(L):
            Zs[p] = sweep(ar, Xs[p], Zs[p], A, pi, act, sx, v["sweep"][p][l])
            if p == p_prime:
                R = ar.a(Xs[p]) - ar.mm(Zs[p] * act[None, :], A)
                Zt, ta, m_t, _ = tail_scan(ar, Zt, ta, R, v["tail"][l],
                                           alpha, sx, sa, N)
                ta = ta * (m_t > 0.5)
                Zt = Zt * ta[None, :]
    out = []
    act_new = None
    for p in range(P):
        Zp, act_new = promote(Zs[p], Zt if p == p_prime else np.zeros_like(Zt),
                              ta, act)
        out.append(Zp)
    Zall = np.stack(out)
    m = np.sum(Zall, axis=(0, 1)) * act_new
    act_out = act_new * (m > 0.5)
    return Zall * act_out[None, None, :], act_out


def master(ar: Arith, Xs, Z, active, sx, sa, mv, hyp, N, D, A_noise=None):
    """The master sync's draws given the post-sync Z and active set:
    {A, pi, sigma_x, sigma_a, alpha}. The noise variances are drawn
    given ``A_noise`` where it is passed (the A of the run under test),
    else given the A drawn here."""
    Zf = ar.a(Z).reshape(-1, Z.shape[-1])
    X = ar.a(Xs).reshape(-1, D)
    act = ar.a(active)
    ZtZ = ar.mm(Zf.T, Zf) * mask_outer(act)
    ZtX = ar.mm(Zf.T, X) * act[:, None]
    ratio = (ar.a(sx) / ar.a(sa)) ** 2
    M = ar.chol_inv(padded_W(ZtZ, act, ratio)) * mask_outer(act)
    mean = ar.mm(M, ZtX) * act[:, None]
    Lc = np.linalg.cholesky(M + np.eye(M.shape[0], dtype=ar.dt) * (1 - act))
    A = mean + ar.a(sx) * (ar.mm(Lc, ar.a(mv["eps"])) * act[:, None])
    pi = ar.a(mv["beta"]) * act
    An = A if A_noise is None else ar.a(A_noise)
    R = X - ar.mm(Zf * act[None, :], An)
    sse = np.sum(R * R)
    sx2 = 1.0 / (ar.a(mv["g_sx"]) / (hyp["b_sx"] + 0.5 * sse))
    k_plus = np.sum(act)
    a_ss = np.sum(An * An * act[:, None])
    sa2 = 1.0 / (ar.a(mv["g_sa"]) / (hyp["b_sa"] + 0.5 * a_ss))
    sigma_a = np.sqrt(sa2) if k_plus > 0 else ar.a(sa)
    HN = sum(1.0 / i for i in range(1, int(N) + 1))
    alpha = ar.a(mv["g_al"]) / (hyp["b_alpha"] + HN)
    return {"A": A, "pi": pi, "sigma_x": np.sqrt(sx2), "sigma_a": sigma_a,
            "alpha": alpha}


# ---------------------------------------------------------------------------
# the bank scorer
# ---------------------------------------------------------------------------


def score_sample(ar: Arith, A, pi, active, sx, X, mask, uu, masked: bool):
    """(probs (B, K), rows_ll (B,)) of B rows under one sample; ``uu``
    (n_sweeps, K, B) uniforms; the later half of the sweeps enter the
    Rao-Blackwellized probabilities."""
    A, X, mask = ar.a(A), ar.a(X), ar.a(mask)
    act = ar.a(active)
    sx = ar.a(sx)
    n_sweeps, K, B = uu.shape
    Am = A * act[:, None]
    Xm = X * mask if masked else X
    F = ar.mm(Am, Am.T) + sx ** 2 * np.eye(K, dtype=ar.dt)
    Lf = np.linalg.cholesky(F)
    y = np.linalg.solve(Lf.T, np.linalg.solve(Lf, ar.mm(Am, Xm.T))).T
    Z = (y > 0.5).astype(ar.dt) * act[None, :]
    Rm = Xm - ar.mm(Z, Am) * mask if masked else Xm - ar.mm(Z, Am)
    an = ar.mm(A * A, mask.T) if masked else np.sum(A * A, axis=1)[:, None]
    lpi = ar.logit(pi, 1e-6)
    u = ar.logit(uu, 1e-7)
    inv2s2 = ar.a(0.5) / sx ** 2
    rb_from = n_sweeps // 2
    probs = np.zeros((B, K), ar.dt)
    for s in range(n_sweeps):
        for k in range(K):
            z_k = Z[:, k]
            dll = (2.0 * (ar.mm(Rm, A[k]) + z_k * an[k]) - an[k]) * inv2s2
            logits = lpi[k] + dll
            znew = (logits > u[s, k]).astype(ar.dt) if act[k] > 0 else z_k
            if s >= rb_from:
                probs[:, k] += act[k] / (1.0 + np.exp(-logits))
            upd = (znew - z_k)[:, None] * A[k][None, :]
            Rm = Rm - (upd * mask if masked else upd)
            Z[:, k] = znew
    probs /= max(n_sweeps - rb_from, 1)
    R = (X - ar.mm(Z, Am)) * mask
    n_obs = np.sum(mask, axis=-1)
    p = np.clip(ar.a(pi), 1e-6, 1.0 - 1e-6)
    lz = Z * np.log(p)[None, :] + (1.0 - Z) * np.log1p(-p)[None, :]
    ll = (-0.5 * n_obs * LOG2PI - n_obs * np.log(sx)
          - 0.5 * np.sum(R * R, axis=-1) / sx ** 2
          + np.sum(lz * act[None, :], axis=-1))
    return probs, ll


def score(ar: Arith, samples, op: str, X, mask, uu):
    """What the op answers for each of the B rows: the mixture
    log-likelihood (``loglik``, (B,)) or the imputed rows (``impute``,
    (B, D)). ``samples``: dict of per-sample A (S, K, D), pi, active
    (S, K), sigma_x (S,); ``uu``: (S, n_sweeps, K, B)."""
    masked = op == "impute"
    S = samples["A"].shape[0]
    out = []
    for s in range(S):
        out.append(score_sample(
            ar, samples["A"][s], samples["pi"][s], samples["active"][s],
            samples["sigma_x"][s], X, mask if masked else np.ones_like(X),
            uu[s], masked))
    if op == "loglik":
        lls = np.stack([o[1] for o in out])
        top = np.max(lls, axis=0)
        return top + np.log(np.sum(np.exp(lls - top), axis=0)) - np.log(S)
    if op == "impute":
        Am = ar.a(samples["A"]) * ar.a(samples["active"])[:, :, None]
        recon = np.mean(np.stack([ar.mm(o[0], Am[s])
                                  for s, o in enumerate(out)]), axis=0)
        m = ar.a(mask)
        return m * ar.a(X) + (1.0 - m) * recon
    raise ValueError(f"op={op!r} has no reference")
