"""Posterior-predictive serving subsystem: SampleBank + batched scoring.

The paper's whole evaluation is posterior-predictive (held-out joint
log-likelihood, Fig. 1), and the ROADMAP north star is *serving* a
posterior, not just producing a trace. This module is the layer that
turns a finished (or in-flight) MCMC run into a usable predictive
model (DESIGN.md §15):

* ``SampleBank`` — a compact, chain-aware ensemble of S post-burn-in
  posterior samples (A, pi, active, sigma_x, sigma_a, alpha, chain, it),
  live-K packed to the §14 bucket ladder (the bank's feature width is
  the smallest power-of-two bucket holding the largest live set across
  its samples, NOT the sampler's K_max). The per-sample Cholesky factor
  chol(Ā Āᵀ + sigma_x² I) used by the encode initializer is computed
  ONCE at harvest time and cached in the bank — neither scoring nor
  bank rebuilds refactorize. Persisted through ``checkpoint.save_arrays`` (npz,
  self-describing) and restorable with no sampler state at all.
* ``encode`` — Rao-Blackwellized posterior feature probabilities
  p(z*_k = 1 | x*, sample) for NEW rows, via per-sample Gibbs passes
  over z* (conditional probabilities averaged over post-burn sweeps);
  ``exact_posterior`` is the 2^K enumeration oracle for small K.
* ``impute`` — E[x_miss | x_obs] under the ensemble by masked-Gaussian
  conditioning: only observed dimensions enter the Gibbs likelihood,
  and E[x_miss | x_obs, s] = E[z | x_obs, s] @ A_s by linearity.
* ``predictive_loglik`` / ``anomaly_score`` — the logsumexp-over-samples
  mixture estimator  log p̂(x*) = logsumexp_s ll_s(x*) − log S  with
  ll_s the per-sample joint log-likelihood (z* imputed by the same
  Gibbs pass — the paper's Fig. 1 "joint log P(X, Z)" metric,
  row-decomposed). ``heldout_joint_loglik`` / ``train_joint_loglik``
  are the ONE canonical implementation of that per-sample metric
  (``diagnostics`` re-exports them; the numpy ``joint_loglik_np`` loop
  survives only as the test oracle).

Every scoring op is jit-compiled and batched over (S samples × B rows):
one dispatch scores the whole ensemble against the whole microbatch.
``predictive_loglik_naive`` keeps the un-batched per-sample loop as the
benchmark baseline (benchmarks/predict.py), and
``make_sharded_scorer`` dispatches a scorer over a mesh "data" axis so
a bank scores row-sharded batches with the same chains×data mesh
machinery the sampler uses.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_arrays, save_arrays

from . import math as ibm
from .sweeps import uncollapsed_sweep

Array = jax.Array

BANK_FORMAT = 1           # bumped on layout changes; load() checks it
DEFAULT_ENCODE_SWEEPS = 8
DEFAULT_LL_SWEEPS = 3     # matches the historical heldout_joint_loglik
ENUM_MAX_K = 16           # 2^K patterns — the exact oracle's hard cap


# --------------------------------------------------------------------------
# the bank
# --------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SampleBank:
    """S posterior samples, feature axis packed to the bucket ladder.

    All leaves are arrays with a leading S axis, so the bank is a pytree
    that jitted scorers close over / take as an argument directly.
    ``chol_f`` is the cached per-sample lower Cholesky factor of
    F_s = Ā_s Ā_sᵀ + sigma_x,s² I (Ā = A masked by ``active``) — the
    ridge map the encode initializer solves against; caching it at
    harvest time is what keeps scoring free of per-call refactorizations.
    """

    A: Array        # (S, K, D)   feature weights (posterior draws)
    pi: Array       # (S, K)      feature probabilities
    active: Array   # (S, K)      live-feature mask (float {0,1})
    sigma_x: Array  # (S,)
    sigma_a: Array  # (S,)
    alpha: Array    # (S,)
    chain: Array    # (S,) int32  which chain the sample came from
    it: Array       # (S,) int32  harvest iteration
    chol_f: Array   # (S, K, K)   cached chol(Ā Āᵀ + sigma_x² I), lower

    @property
    def S(self) -> int:
        return self.A.shape[0]

    @property
    def K(self) -> int:
        return self.A.shape[1]

    @property
    def D(self) -> int:
        return self.A.shape[2]

    # ---- persistence (self-describing npz; no sampler state involved) ----
    def save(self, path: str) -> str:
        arrs = {f.name: np.asarray(getattr(self, f.name))
                for f in dataclasses.fields(self)}
        arrs["_format"] = np.asarray(BANK_FORMAT, np.int32)
        return save_arrays(path, arrs)

    @classmethod
    def load(cls, path: str) -> "SampleBank":
        arrs = load_arrays(path)
        fmt = int(arrs.pop("_format", 0))
        if fmt != BANK_FORMAT:
            raise ValueError(
                f"sample bank {path} has format {fmt}, expected "
                f"{BANK_FORMAT} — re-harvest with this version"
            )
        names = {f.name for f in dataclasses.fields(cls)}
        missing = names - set(arrs)
        if missing:
            raise ValueError(f"sample bank {path} is missing {sorted(missing)}")
        return cls(**{k: jnp.asarray(v) for k, v in arrs.items()
                      if k in names})


class BankBuilder:
    """Host-side harvest accumulator: compacts each sample's live
    features (canonical order preserved) and packs the bank to the §14
    bucket ladder at build time.

    The driver calls ``add_state`` at harvest cadence (chain-aware: a
    chain-batched state contributes one sample per chain), then
    ``build()`` — which pads every sample to the bank bucket (smallest
    power-of-two bucket ≥ the largest live set). Each sample's encode
    factor chol(Ā Āᵀ + σ_x² I) is computed ONCE at ``add`` time on the
    live block only: the full-width matrix is block-diagonal (dead rows
    of Ā are zero), so padding the factor is an exact embedding —
    live-block chol in the corner, σ_x on the dead diagonal. ``build``
    therefore does no linear algebra and no jit, so the driver can
    rebuild the bank at every checkpoint cadence for free.
    """

    def __init__(self, K_max: int):
        self.K_max = int(K_max)
        self._rows: list[dict] = []

    def __len__(self) -> int:
        return len(self._rows)

    @property
    def max_live(self) -> int:
        return max((r["A"].shape[0] for r in self._rows), default=0)

    def add(self, A, pi, active, sigma_x, sigma_a, alpha,
            chain: int = 0, it: int = 0, chol=None) -> None:
        """One posterior sample in canonical (K_max-padded) layout.

        ``chol`` is the live-block encode factor when the caller already
        has it (``extend_from`` — a restart must not refactorize);
        freshly harvested samples compute it here, once."""
        act = np.asarray(active, np.float32)
        live = np.flatnonzero(act > 0.5)
        sx = float(sigma_x)
        if chol is None:
            Al = np.asarray(A, np.float32)[live].astype(np.float64)
            chol = np.linalg.cholesky(Al @ Al.T + sx**2 * np.eye(len(live)))
        self._rows.append({
            "A": np.asarray(A, np.float32)[live],
            "pi": np.asarray(pi, np.float32)[live],
            "chol": np.asarray(chol, np.float32),
            "sigma_x": sx, "sigma_a": float(sigma_a),
            "alpha": float(alpha), "chain": int(chain), "it": int(it),
        })

    def add_state(self, gs, it: int = 0) -> int:
        """Harvest from a HybridGlobal (chainless or chain-batched).

        Returns the number of samples added (== n_chains)."""
        A = np.asarray(gs.A)
        if A.ndim == 3:  # chain-batched
            pi, act = np.asarray(gs.pi), np.asarray(gs.active)
            sx, sa = np.asarray(gs.sigma_x), np.asarray(gs.sigma_a)
            al = np.asarray(gs.alpha)
            for c in range(A.shape[0]):
                self.add(A[c], pi[c], act[c], sx[c], sa[c], al[c],
                         chain=c, it=it)
            return A.shape[0]
        self.add(A, gs.pi, gs.active, gs.sigma_x, gs.sigma_a, gs.alpha,
                 chain=0, it=it)
        return 1

    def extend_from(self, bank: SampleBank) -> int:
        """Re-seed the builder from a persisted bank (driver restarts:
        harvesting continues across crash/growth restarts instead of
        overwriting the bank with a shorter ensemble). The cached encode
        factors come along — a built bank keeps live features in the
        leading slots, so each factor's live block is its top-left
        corner and nothing is refactorized."""
        chol = np.asarray(bank.chol_f)
        for s in range(bank.S):
            k = int(np.sum(np.asarray(bank.active[s]) > 0.5))
            self.add(bank.A[s], bank.pi[s], bank.active[s],
                     bank.sigma_x[s], bank.sigma_a[s], bank.alpha[s],
                     chain=int(bank.chain[s]), it=int(bank.it[s]),
                     chol=chol[s, :k, :k])
        return bank.S

    def prune_after(self, it: int) -> int:
        """Drop samples harvested AFTER iteration ``it``. Restart
        reconciliation: a restore rewinds the chain to its checkpoint
        step and re-runs the iterations since, which re-harvests the
        same draws — pruning to the restored step first keeps every
        sample exactly once. Returns the number dropped."""
        n0 = len(self._rows)
        self._rows = [r for r in self._rows if r["it"] <= it]
        return n0 - len(self._rows)

    def build(self) -> SampleBank:
        if not self._rows:
            raise ValueError("empty bank: no samples harvested (is "
                             "harvest_every set and past harvest_burn?)")
        buckets = ibm.live_buckets(self.K_max)
        B = ibm.pick_bucket(buckets, self.max_live, 0)
        S = len(self._rows)
        D = self._rows[0]["A"].shape[1]  # (0, D) even with no live features
        A = np.zeros((S, B, D), np.float32)
        pi = np.zeros((S, B), np.float32)
        act = np.zeros((S, B), np.float32)
        chol = np.zeros((S, B, B), np.float32)
        for s, r in enumerate(self._rows):
            k = r["A"].shape[0]
            A[s, :k] = r["A"]
            pi[s, :k] = r["pi"]
            act[s, :k] = 1.0
            # exact block-diagonal embedding of the add-time factor
            chol[s, :k, :k] = r["chol"]
            chol[s, range(k, B), range(k, B)] = r["sigma_x"]
        bank = SampleBank(
            A=jnp.asarray(A), pi=jnp.asarray(pi), active=jnp.asarray(act),
            sigma_x=jnp.asarray([r["sigma_x"] for r in self._rows],
                                dtype=np.float32),
            sigma_a=jnp.asarray([r["sigma_a"] for r in self._rows],
                                dtype=np.float32),
            alpha=jnp.asarray([r["alpha"] for r in self._rows],
                              dtype=np.float32),
            chain=jnp.asarray([r["chain"] for r in self._rows],
                              dtype=np.int32),
            it=jnp.asarray([r["it"] for r in self._rows], dtype=np.int32),
            chol_f=jnp.asarray(chol),
        )
        return bank


# --------------------------------------------------------------------------
# per-sample core: masked Rao-Blackwellized Gibbs over z*
# --------------------------------------------------------------------------


def _logit(p: Array) -> Array:
    p = jnp.clip(p, 1e-6, 1.0 - 1e-6)
    return jnp.log(p) - jnp.log1p(-p)


def _gibbs_encode_one(A, pi, active, sigma_x, chol_f, X, mask, key,
                      n_sweeps: int, rb_from: int, masked: bool = True):
    """Masked Gibbs over z for B rows under ONE posterior sample.

    Returns (probs (B, K), Z (B, K)): ``probs`` is the Rao-Blackwellized
    marginal estimate — the conditional p(z_k = 1 | z_-k, x_obs)
    evaluated at each bit's resample, averaged over sweeps
    ``rb_from .. n_sweeps-1`` — and ``Z`` the final Gibbs draw.

    Only observed dimensions (mask = 1) enter the likelihood: the
    carried residual is masked, and the per-bit |a_k|² is the masked
    row-wise norm — exactly conditioning the Gaussian on x_obs.
    The chain starts from the cached ridge map (bank ``chol_f``): z0 =
    1[F⁻¹ Ā x_obs > 1/2], a deterministic warm start that costs one
    cached triangular solve, never a factorization.

    Hot-path shape discipline (what makes the (S × B) batching ≥ 5x the
    per-sample loop, benchmarks/predict.py): the masked per-bit norms
    ‖a_k‖²_obs collapse to ONE up-front (B, K) GEMM (mask is 0/1, so
    masked-square = mask @ (A∘A)ᵀ), and the per-bit likelihood delta is
    a GEMV against the carried masked residual — with the identity
    R0·a_obs = Rm·a_k + z_k ‖a_k‖²_obs there is no (B, D) temporary on
    the bit step beyond the single fused residual update. Under the
    vmap over S these GEMVs batch into one einsum per bit.
    """
    B, D = X.shape
    K = A.shape[0]
    Am = A * active[:, None]
    Xm = X * mask if masked else X
    # ridge warm start from the cached factor
    y = jax.scipy.linalg.cho_solve((chol_f, True), Am @ Xm.T).T  # (B, K)
    Z = (y > 0.5).astype(X.dtype) * active[None, :]
    Rm = Xm - (Z @ Am) * mask if masked else Xm - Z @ Am
    # fully-observed rows share one ‖a_k‖² per feature — ``masked`` is a
    # TRACE-TIME branch, so the unmasked hot path (serving loglik /
    # anomaly on complete rows) never materializes per-row norms nor
    # pays the two extra (B, D) mask passes per bit step
    anorm2_t = ((A * A) @ mask.T if masked
                else jnp.sum(A * A, axis=1)[:, None])  # (K, B) | (K, 1)
    lpi = _logit(pi)
    inv2s2 = 0.5 / (sigma_x**2)
    uu = jax.random.uniform(key, (n_sweeps, K, B), dtype=X.dtype)
    u = _logit(jnp.clip(uu, 1e-7, 1.0 - 1e-7))

    # Everything the bit step reads rides the scan's xs (no dynamic
    # gathers), and Z is REBUILT from the scan's stacked outputs instead
    # of per-bit column scatters: a bit step touches other bits only
    # through the carried residual, and its own column was last written
    # one full sweep ago — so the sweep-entry Z.T is a valid xs.
    def sweep(carry, u_s):
        Rm, Zt = carry  # Zt: (K, B), sweep-entry transpose

        def bit(Rm, xs):
            a_k, an, lpi_k, act_k, u_k, z_k = xs
            # R0·(a_k ∘ mask) = Rm·a_k + z_k ‖a_k‖²_obs  (Rm is masked)
            dll = (2.0 * (Rm @ a_k + z_k * an) - an) * inv2s2
            logits = lpi_k + dll
            znew = jnp.where(act_k > 0, (logits > u_k).astype(Rm.dtype),
                             z_k)
            prob = jax.nn.sigmoid(logits) * act_k
            upd = (znew - z_k)[:, None] * a_k[None, :]
            Rm = Rm - (upd * mask if masked else upd)
            return Rm, (znew, prob)

        Rm, (Zt, probs) = jax.lax.scan(
            bit, Rm, (A, anorm2_t, lpi, active, u_s, Zt))
        return (Rm, Zt), probs  # (K, B)

    (Rm, Zt), probs_all = jax.lax.scan(sweep, (Rm, Z.T), u)
    denom = max(n_sweeps - rb_from, 1)
    w = (jnp.arange(n_sweeps) >= rb_from).astype(X.dtype) / denom
    probs = jnp.einsum("s,skb->bk", w, probs_all)
    return probs, Zt.T


def _rows_joint_loglik(A, pi, active, sigma_x, X, Z, mask):
    """Per-row joint log p(x_obs, z | sample), (B,). Pure jnp — the
    (S, B)-batched building block of every mixture estimator here."""
    Am = A * active[:, None]
    R = (X - Z @ Am) * mask
    n_obs = jnp.sum(mask, axis=-1)
    ll = (-0.5 * n_obs * ibm.LOG2PI - n_obs * jnp.log(sigma_x)
          - 0.5 * jnp.sum(R * R, axis=-1) / sigma_x**2)
    p = jnp.clip(pi, 1e-6, 1.0 - 1e-6)
    lz = Z * jnp.log(p)[None, :] + (1.0 - Z) * jnp.log1p(-p)[None, :]
    return ll + jnp.sum(lz * active[None, :], axis=-1)


def _score_one(A, pi, active, sigma_x, chol_f, X, mask, key,
               n_sweeps: int, rb_from: int, masked: bool = True):
    """(probs, Z, rows_ll) for one sample — the vmapped-over-S core."""
    probs, Z = _gibbs_encode_one(A, pi, active, sigma_x, chol_f, X, mask,
                                 key, n_sweeps, rb_from, masked)
    ll = _rows_joint_loglik(A, pi, active, sigma_x, X, Z, mask)
    return probs, Z, ll


@partial(jax.jit, static_argnames=("n_sweeps", "rb_from", "masked"))
def _score_bank(bank: SampleBank, X: Array, mask: Array, key: Array,
                n_sweeps: int, rb_from: int, masked: bool = True):
    """THE batched scorer: one jitted dispatch over (S samples × B rows).

    Returns (probs (S, B, K), Z (S, B, K), rows_ll (S, B))."""
    keys = jax.random.split(key, bank.A.shape[0])
    one = partial(_score_one, n_sweeps=n_sweeps, rb_from=rb_from,
                  masked=masked)
    return jax.vmap(
        one, in_axes=(0, 0, 0, 0, 0, None, None, 0)
    )(bank.A, bank.pi, bank.active, bank.sigma_x, bank.chol_f,
      X, mask, keys)


def _as_mask(X: Array, mask) -> Array:
    return jnp.ones_like(X) if mask is None else jnp.asarray(mask, X.dtype)


# --------------------------------------------------------------------------
# public predictive ops
# --------------------------------------------------------------------------


def encode(bank: SampleBank, X, key, *, mask=None,
           n_sweeps: int = DEFAULT_ENCODE_SWEEPS,
           return_draws: bool = False):
    """Rao-Blackwellized p(z*_k = 1 | x*, sample) for new rows.

    Returns (S, B, K) posterior feature probabilities (one slice per
    bank sample); with ``return_draws`` also the final Gibbs draws
    (S, B, K). ``mask`` (B, D) marks observed dimensions (None = all)."""
    X = jnp.asarray(X)
    probs, Z, _ = _score_bank(bank, X, _as_mask(X, mask), key,
                              n_sweeps, n_sweeps // 2,
                              masked=mask is not None)
    return (probs, Z) if return_draws else probs


def impute(bank: SampleBank, X, mask, key, *,
           n_sweeps: int = DEFAULT_ENCODE_SWEEPS):
    """E[x | x_obs] under the ensemble; observed entries pass through.

    Masked-Gaussian conditioning: the Gibbs pass conditions z on the
    observed dimensions only, and by linearity E[x_miss | x_obs, s] =
    E[z | x_obs, s] @ A_s — the RB probabilities are exactly that
    conditional mean estimate. Ensemble = mean over samples."""
    X = jnp.asarray(X)
    m = _as_mask(X, mask)
    probs, _, _ = _score_bank(bank, X, m, key, n_sweeps, n_sweeps // 2,
                              masked=mask is not None)
    recon = jnp.mean(
        jnp.einsum("sbk,skd->sbd", probs,
                   bank.A * bank.active[:, :, None]), axis=0)
    return m * X + (1.0 - m) * recon


def predictive_loglik(bank: SampleBank, X, key, *, mask=None,
                      n_sweeps: int = DEFAULT_LL_SWEEPS,
                      per_sample: bool = False):
    """Mixture estimator log p̂(x*_b) = logsumexp_s ll_sb − log S, (B,).

    ll_sb is the per-sample joint log-likelihood with z* imputed by the
    per-sample Gibbs pass (the paper's Fig. 1 metric, row-decomposed) —
    the canonical replacement for the old per-sample-only
    ``heldout_joint_loglik``. ``per_sample`` additionally returns the
    (S, B) per-sample rows for diagnostics."""
    X = jnp.asarray(X)
    _, _, lls = _score_bank(bank, X, _as_mask(X, mask), key,
                            n_sweeps, n_sweeps // 2,
                            masked=mask is not None)
    mix = jax.scipy.special.logsumexp(lls, axis=0) - jnp.log(lls.shape[0])
    return (mix, lls) if per_sample else mix


def anomaly_score(bank: SampleBank, X, key, *, mask=None,
                  n_sweeps: int = DEFAULT_LL_SWEEPS):
    """Per-row anomaly score = − mixture predictive log-likelihood."""
    return -predictive_loglik(bank, X, key, mask=mask, n_sweeps=n_sweeps)


@partial(jax.jit, static_argnames=("n_sweeps",))
def _naive_sample_rows(A, pi, active, sigma_x, X, key,
                       n_sweeps: int) -> Array:
    """Per-row joint ll for ONE sample the pre-§15 way: a cold-start
    uncollapsed Gibbs imputation of z* (exactly ``heldout_joint_loglik``'s
    inner loop) followed by the row-decomposed joint. One jit dispatch
    per sample — the serving anti-pattern the batched scorer replaces."""
    B, D = X.shape
    K = A.shape[0]
    Z = jnp.zeros((B, K), X.dtype)

    def body(Z, l):
        Z = uncollapsed_sweep(
            X, Z, A, pi, active, sigma_x, jax.random.fold_in(key, l)
        )
        return Z, None

    Z, _ = jax.lax.scan(body, Z, jnp.arange(n_sweeps))
    return _rows_joint_loglik(A, pi, active, sigma_x, X, Z,
                              jnp.ones_like(X))


def predictive_loglik_naive(bank: SampleBank, X, key, *,
                            n_sweeps: int = DEFAULT_LL_SWEEPS):
    """The un-batched baseline: a python loop dispatching one jitted
    per-sample scorer per bank sample — ensemble scoring as it existed
    before this subsystem (S sequential ``heldout_joint_loglik``-style
    evaluations), row-decomposed and logsumexp-mixed the same way.
    benchmarks/predict.py measures the batched scorer against THIS."""
    X = jnp.asarray(X)
    keys = jax.random.split(key, bank.S)
    out = []
    for s in range(bank.S):
        out.append(_naive_sample_rows(
            bank.A[s], bank.pi[s], bank.active[s], bank.sigma_x[s],
            X, keys[s], n_sweeps))
    lls = jnp.stack(out)
    return jax.scipy.special.logsumexp(lls, axis=0) - jnp.log(bank.S)


def make_sharded_scorer(bank: SampleBank, mesh, *, axis: str = "data",
                        n_sweeps: int = DEFAULT_LL_SWEEPS):
    """Row-sharded mixture scoring over a mesh ``axis`` — the serving
    analogue of the sampler's data axis: the bank is replicated, the
    batch rows are sharded, and each shard folds its axis index into
    the key so shards draw independent Gibbs streams.

    Returns ``score(X, key) -> (B,)`` (jitted; B must divide the axis
    size)."""
    from jax.sharding import PartitionSpec as P

    def block(X_p, key):
        k = jax.random.fold_in(key, jax.lax.axis_index(axis))
        return predictive_loglik(bank, X_p, k, n_sweeps=n_sweeps)

    fn = jax.shard_map(
        block, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(fn)


# --------------------------------------------------------------------------
# exact small-K enumeration oracle
# --------------------------------------------------------------------------


def exact_posterior(A, pi, active, sigma_x, X, mask=None):
    """Exact p(z* | x*_obs) by 2^K enumeration (K ≤ ENUM_MAX_K).

    Returns (marginals (B, K), log_marginal_lik (B,), cond_mean (B, D)):
    the exact Rao-Blackwell targets ``encode`` / ``predictive_loglik`` /
    ``impute`` estimate. Patterns that set an inactive bit are excluded
    (weight −inf), so the enumeration runs over the live set exactly."""
    A = jnp.asarray(A)
    K, D = A.shape
    if K > ENUM_MAX_K:
        raise ValueError(f"exact enumeration needs K <= {ENUM_MAX_K}, "
                         f"got {K}")
    X = jnp.asarray(X)
    m = _as_mask(X, mask)
    return _exact_posterior_jit(A, jnp.asarray(pi), jnp.asarray(active),
                                jnp.asarray(sigma_x), X, m)


@jax.jit
def _exact_posterior_jit(A, pi, active, sigma_x, X, mask):
    K, D = A.shape
    pats = ((jnp.arange(2**K)[:, None] >> jnp.arange(K)[None, :]) & 1
            ).astype(X.dtype)                                   # (P, K)
    valid = jnp.all(pats <= active[None, :] + 0.5, axis=1)
    p = jnp.clip(pi, 1e-6, 1.0 - 1e-6)
    prior = jnp.sum((pats * jnp.log(p)[None, :]
                     + (1.0 - pats) * jnp.log1p(-p)[None, :])
                    * active[None, :], axis=1)                  # (P,)
    means = pats @ (A * active[:, None])                        # (P, D)
    # masked Gaussian: sum over observed dims only
    R = X[None, :, :] - means[:, None, :]                       # (P, B, D)
    sse = jnp.sum(R * R * mask[None, :, :], axis=-1)            # (P, B)
    n_obs = jnp.sum(mask, axis=-1)[None, :]
    ll = (-0.5 * n_obs * ibm.LOG2PI - n_obs * jnp.log(sigma_x)
          - 0.5 * sse / sigma_x**2)
    logw = jnp.where(valid[:, None], prior[:, None] + ll, -jnp.inf)
    logZ = jax.scipy.special.logsumexp(logw, axis=0)            # (B,)
    w = jnp.exp(logw - logZ[None, :])                           # (P, B)
    marg = jnp.einsum("pb,pk->bk", w, pats)
    cond_mean = jnp.einsum("pb,pd->bd", w, means)
    return marg, logZ, cond_mean


# --------------------------------------------------------------------------
# canonical per-sample joint log-likelihoods (diagnostics re-exports)
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("n_sweeps",))
def heldout_joint_loglik(
    X_test: Array,
    A: Array,
    pi: Array,
    active: Array,
    sigma_x: Array,
    key: Array,
    n_sweeps: int = DEFAULT_LL_SWEEPS,
) -> Array:
    """log P(X_test, Z_test | A, pi, sigma) with Z_test imputed by short
    uncollapsed Gibbs given ONE posterior draw (paper Fig. 1 metric).

    Identical algorithm + PRNG stream to the pre-§15 implementation in
    ``diagnostics`` (which now re-exports this); the residual scoring
    runs through the ``gaussian_sse`` kernel family. For ensemble
    (multi-sample) scoring use ``predictive_loglik`` — the logsumexp
    mixture over a SampleBank."""
    from repro.kernels.gaussian_sse import gaussian_sse

    N, D = X_test.shape
    K = A.shape[0]
    Z = jnp.zeros((N, K), X_test.dtype)

    def body(Z, l):
        Z = uncollapsed_sweep(
            X_test, Z, A, pi, active, sigma_x, jax.random.fold_in(key, l)
        )
        return Z, None

    Z, _ = jax.lax.scan(body, Z, jnp.arange(n_sweeps))
    n = X_test.size
    sse = gaussian_sse(X_test, Z, A, active)
    ll = (-0.5 * n * ibm.LOG2PI - n * jnp.log(sigma_x)
          - 0.5 * sse / sigma_x**2)
    return ll + ibm.z_prior_loglik(Z, pi, active)


def train_joint_loglik(
    X: Array, Z: Array, A: Array, pi: Array, active: Array, sigma_x: Array
) -> Array:
    """log P(X, Z | A, pi, sigma) on the training rows (monitoring)."""
    ll = ibm.uncollapsed_loglik(X, Z * active[None, :], A, sigma_x)
    return ll + ibm.z_prior_loglik(Z, pi, active)


# --------------------------------------------------------------------------
# numpy test oracle (NOT a production path)
# --------------------------------------------------------------------------


def joint_loglik_np(X, Z, A, pi, active, sigma_x, mask=None) -> np.ndarray:
    """Per-row joint log p(x_obs, z | sample) as an explicit float64
    numpy loop — the test oracle ``_rows_joint_loglik`` is checked
    against (tests/test_predict.py). Kept deliberately naive."""
    X = np.asarray(X, np.float64)
    Z = np.asarray(Z, np.float64)
    A = np.asarray(A, np.float64)
    pi = np.asarray(pi, np.float64)
    active = np.asarray(active, np.float64)
    sx = float(sigma_x)
    m = np.ones_like(X) if mask is None else np.asarray(mask, np.float64)
    B, D = X.shape
    out = np.zeros((B,), np.float64)
    log2pi = float(np.log(2.0 * np.pi))
    for b in range(B):
        ll = 0.0
        for d in range(D):
            if m[b, d] > 0.5:
                r = X[b, d] - float(
                    sum(Z[b, k] * active[k] * A[k, d]
                        for k in range(A.shape[0])))
                ll += -0.5 * log2pi - np.log(sx) - 0.5 * r * r / sx**2
        for k in range(A.shape[0]):
            if active[k] > 0.5:
                p = min(max(pi[k], 1e-6), 1.0 - 1e-6)
                ll += (Z[b, k] * np.log(p)
                       + (1.0 - Z[b, k]) * np.log1p(-p))
        out[b] = ll
    return out
