"""Linear-Gaussian IBP math: marginal likelihoods, conjugate posteriors, rank updates.

Model (paper Eq. 1):
    X = Z A + eps,   eps ~ N(0, sigma_x^2 I),   A_k ~ N(0, sigma_a^2 I)

All feature-indexed buffers are padded to a static ``K_max``; an ``active``
mask (float {0,1}) selects live columns.  Inactive rows/cols are arranged so
that padded linear algebra (Cholesky of W) is exact: the padded W gets unit
diagonal / zero off-diagonal in inactive slots, contributing 0 to logdet and
nothing to the trace term.

Every f32 product of the collapsed sampler goes through ``dot`` below, at
``precision="highest"`` (the collapsed_row kernels pass it too). A TPU
otherwise runs it as one bf16 pass, about three significant digits: on
a v5e the carried Cholesky factor and inverse then drift past the
monitor's tolerance (87 refreshes where the cadence schedules 15, at
N=1000) and the fast chain leaves the ref oracle (DESIGN.md §12).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

LOG2PI = float(jnp.log(2.0 * jnp.pi))


def dot(a: Array, b: Array) -> Array:
    """``jnp.dot`` at f32 precision (see the module docstring)."""
    return jnp.dot(a, b, precision="highest")


def mask_outer(active: Array) -> Array:
    """(K,K) mask with 1 where both row & col active."""
    return active[:, None] * active[None, :]


def padded_W(ZtZ: Array, active: Array, ratio: Array) -> Array:
    """W = ZtZ + ratio*I on active block; identity on inactive block.

    ratio = sigma_x^2 / sigma_a^2.
    """
    K = ZtZ.shape[0]
    m2 = mask_outer(active)
    W = ZtZ * m2 + ratio * jnp.eye(K) * active[:, None] * active[None, :]
    # inactive diagonal -> 1 so chol / logdet are well defined and contribute 0
    W = W + jnp.eye(K) * (1.0 - active)
    return W


def chol_inv_logdet(W: Array) -> tuple[Array, Array]:
    """Return (W^{-1}, logdet W) via Cholesky. W must be SPD."""
    L = jnp.linalg.cholesky(W)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(L)))
    eye = jnp.eye(W.shape[0], dtype=W.dtype)
    Linv = jax.scipy.linalg.solve_triangular(L, eye, lower=True)
    Winv = dot(Linv.T, Linv)
    return Winv, logdet


def chol_inv(W: Array) -> tuple[Array, Array]:
    """Return (L, W^{-1}) via Cholesky — the exact-refactorization form the
    fast collapsed row step refreshes its carried (L, M) from."""
    L = jnp.linalg.cholesky(W)
    eye = jnp.eye(W.shape[0], dtype=W.dtype)
    Linv = jax.scipy.linalg.solve_triangular(L, eye, lower=True)
    return L, dot(Linv.T, Linv)


def collapsed_loglik(
    trXtX: Array,
    ZtX: Array,
    ZtZ: Array,
    active: Array,
    N: Array,
    D: int,
    sigma_x: Array,
    sigma_a: Array,
) -> Array:
    """log P(X | Z) with A integrated out (paper Sec. 2 / G&G 2011 Eq. 26).

    log P = -(N D / 2) log(2 pi) - (N - K) D log sigma_x - K D log sigma_a
            - (D/2) log|W| - (1 / 2 sigma_x^2) ( tr(X^T X) - tr(X^T Z M Z^T X) )
    with W = Z^T Z + (sigma_x^2/sigma_a^2) I,  M = W^{-1}.

    All feature inputs are K_max padded + masked by ``active``.
    """
    ratio = (sigma_x / sigma_a) ** 2
    K = jnp.sum(active)
    W = padded_W(ZtZ, active, ratio)
    M, logdetW = chol_inv_logdet(W)
    ZtX_m = ZtX * active[:, None]
    quad = jnp.sum(dot(M, ZtX_m) * ZtX_m)  # tr( (ZtX)^T M (ZtX) )
    Nf = N.astype(jnp.float32) if hasattr(N, "astype") else jnp.float32(N)
    return (
        -0.5 * Nf * D * LOG2PI
        - (Nf - K) * D * jnp.log(sigma_x)
        - K * D * jnp.log(sigma_a)
        - 0.5 * D * logdetW
        - 0.5 / (sigma_x**2) * (trXtX - quad)
    )


def sm_downdate(M: Array, z: Array) -> tuple[Array, Array]:
    """Sherman-Morrison removal: M' = (W - z z^T)^{-1} given M = W^{-1}.

    Returns (M', log det(W - z z^T) - log det W) = (M', log(1 - z^T M z)).
    """
    Mz = dot(M, z)
    denom = 1.0 - dot(z, Mz)
    return M + jnp.outer(Mz, Mz) / denom, jnp.log(denom)


def sm_update(M: Array, z: Array) -> tuple[Array, Array]:
    """Sherman-Morrison addition: M' = (W + z z^T)^{-1}; logdet delta = log(1+z^T M z)."""
    Mz = dot(M, z)
    denom = 1.0 + dot(z, Mz)
    return M - jnp.outer(Mz, Mz) / denom, jnp.log(denom)


def _chol_rank1_t(Lt: Array, p: Array, sigma: float, eps: float) -> tuple[Array, Array]:
    """Core of the rank-one Cholesky up/downdate, transposed layout.

    Closed "semiseparable" form (Gill, Golub, Murray & Saunders Method C /
    Seeger 2004): with p = L^{-1} x,

        chol(L L^T + sigma x x^T) = L * chol(I + sigma p p^T)

    and chol(I + sigma p p^T) has entries T[j,j] = sqrt(d_j / d_{j-1}),
    T[i>j, j] = sigma p_i p_j / sqrt(d_j d_{j-1}) with d_j = 1 + sigma
    cumsum(p^2)_j — so the whole move is a cumulative sum + elementwise
    work: O(K^2) in dense vectorized ops with no sequential K-loop (the
    LINPACK column-rotation form is also O(K^2) but serializes K dependent
    steps, which is what dominates wall-time on CPU/TPU at our K).

    Works on Lt = L^T (upper triangular, row-major) so every pass —
    the cumulative sum over source columns in particular — runs along
    contiguous rows: (L T)^T[j] = r_j Lt[j] + sigma-coef_j * sum_{i>j}
    p_i Lt[i], and the exclusive tail sum is (p @ Lt) - inclusive-cumsum.

    Returns (Lt', ok): ``ok`` is False when some d_j fell below ``eps``,
    i.e. the downdated matrix lost positive definiteness.

    Padding contract: a padded/inactive slot j has Lt[j, j] = 1 with zero
    off-diagonals AND p_j = 0 (callers mask the rank-one vector by the
    active mask); then the slot's row scales by exactly 1 and receives
    exactly 0 — padding-transparent, no masked variant needed.
    """
    K = Lt.shape[0]
    p2 = p * p
    d = 1.0 + sigma * jnp.cumsum(p2)
    d_prev = d - sigma * p2  # d_{j-1} with d_{-1} = 1
    ok = jnp.all(d > eps) & jnp.all(d_prev > eps)
    d = jnp.maximum(d, eps)
    d_prev = jnp.maximum(d_prev, eps)
    r = jnp.sqrt(d / d_prev)               # diagonal of chol(I + sigma p p^T)
    qc = sigma * p / jnp.sqrt(d * d_prev)  # tail coefficient per column
    Gt = Lt * p[:, None]
    # Ct[j] = sum_{i > j} p_i Lt[i] — exclusive tail sums over rows. The
    # prefix sums go through a GEMM against a constant lower-triangular
    # ones matrix rather than jnp.cumsum: on CPU/TPU the K^3 matmul beats
    # the K^2 scan-lowered cumsum by ~2x at our K (BLAS/MXU vs serial scan)
    tril = jnp.tril(jnp.ones((K, K), Lt.dtype))
    acc = dot(tril, Gt)
    Ct = acc[-1][None, :] - acc
    return Lt * r[:, None] + Ct * qc[:, None], ok


def chol_rank1_update_t(Lt: Array, p: Array) -> Array:
    """Transposed-layout rank-one update with precomputed p = L^{-1} x.

    The hot-path form: the fast collapsed row step already carries
    M = W^{-1}, so p = L^T (M x) is a matvec — no triangular solve. The
    update direction cannot lose positive definiteness: no canary.
    """
    Lp, _ = _chol_rank1_t(Lt, p, 1.0, 1e-12)
    return Lp


def chol_rank1_downdate_t(Lt: Array, p: Array, eps: float = 1e-12) -> tuple[Array, Array]:
    """Transposed-layout rank-one downdate with precomputed p = L^{-1} x.

    Returns (Lt', ok); ``ok`` False = positive definiteness lost (see
    ``chol_rank1_downdate``).
    """
    return _chol_rank1_t(Lt, p, -1.0, eps)


def chol_rank1_update(L: Array, x: Array) -> Array:
    """Rank-one Cholesky update: chol(L L^T + x x^T) in O(K^2) vector ops.

    Standalone (lower-triangular) form: does its own triangular solve for
    p. See ``_chol_rank1_t`` for the algebra + padding contract.
    """
    p = jax.scipy.linalg.solve_triangular(L, x, lower=True)
    return chol_rank1_update_t(L.T, p).T


def chol_rank1_downdate(L: Array, x: Array, eps: float = 1e-12) -> tuple[Array, Array]:
    """Rank-one Cholesky downdate: chol(L L^T - x x^T), with a canary.

    Returns (L', ok). ``ok`` is False when some partial d_j = 1 -
    cumsum(p^2)_j fell below ``eps`` — i.e. the implied matrix lost
    positive definiteness. Mathematically this never happens for our
    W - z z^T (removing a row keeps W ⪰ (sigma_x/sigma_a)^2 I), so a False
    here is a float-drift detector: the caller must refresh from the exact
    sufficient statistics. See ``_chol_rank1_t`` for algebra + padding.
    """
    p = jax.scipy.linalg.solve_triangular(L, x, lower=True)
    Lt, ok = chol_rank1_downdate_t(L.T, p, eps)
    return Lt.T, ok


def g_rank1(G: Array, H: Array, a: Array, b: Array) -> Array:
    """Move G = H Hᵀ through the rank-one map move H' = H + a bᵀ.

    G' = (H + a bᵀ)(H + a bᵀ)ᵀ = G + a(Hb)ᵀ + (Hb)aᵀ + (b·b) a aᵀ —
    a symmetric rank-two correction costing O(K² + KD), vs the O(K²D)
    G recompute it replaces in the packed collapsed flip (DESIGN.md §14).
    ``H`` is the PRE-move map (the same H the Sherman–Morrison move read).

    Evaluated as a cᵀ + c aᵀ with c = Hb + (b·b)/2 · a, so the result is
    EXACTLY symmetric whenever G is (a_i c_j + c_i a_j is commutative in
    float) — the packed flip reads G rows as columns.

    Padding contract: a padded/inactive slot j has H[j] = 0 and a_j = 0
    (callers mask the rank-one vector), so row/col j of every correction
    term is exactly 0 — padding-transparent, like the chol moves.
    """
    c = dot(H, b) + (0.5 * dot(b, b)) * a
    return G + (jnp.outer(a, c) + jnp.outer(c, a))


# --------------------------------------------------------------------------
# occupancy-adaptive packing: K_live bucket policy + block permutations
# (DESIGN.md §14)
# --------------------------------------------------------------------------


def live_buckets(K_max: int, base: int = 8) -> tuple[int, ...]:
    """Power-of-two K_live block sizes (8, 16, 32, ...) capped by K_max.

    K_max itself is always the last bucket, so a full-occupancy chain
    degenerates to today's unpacked layout; the bucket count is
    O(log K_max), which bounds the jit compile cache of the packed scan.
    """
    if K_max < 1:
        raise ValueError(f"K_max={K_max} must be >= 1")
    bs = []
    b = base
    while b < K_max:
        bs.append(b)
        b *= 2
    bs.append(K_max)
    return tuple(bs)


def pick_bucket(buckets: tuple[int, ...], k_plus: int, headroom: int) -> int:
    """Smallest bucket with room for ``k_plus`` live features + headroom.

    Host-side policy: ``headroom`` in-block free slots guarantee the next
    per-row birth (j_new <= J_MAX) fits without a repack; when nothing
    fits, the largest bucket (== K_max) is returned — at full width the
    packed scan can never overflow.
    """
    for b in buckets:
        if b >= k_plus + headroom:
            return b
    return buckets[-1]


def block_select(active: Array, B: int) -> tuple[Array, Array]:
    """Canonical columns of the packed K_live block, ascending.

    The block is every live column plus the LOWEST-index free slots
    filling up to ``B`` — so in-canonical-order iteration over the block
    visits live columns in the oracle's order, and new-dish placement
    into the block's free slots matches the oracle's first-free-slot rule
    as long as the birth stays below ``min_out`` (the smallest
    out-of-block canonical index; every out-of-block slot is free by
    construction). Requires sum(active) <= B, which the bucket policy
    guarantees host-side.

    Returns (cols (B,) int32, min_out () int32 — K when the block covers
    everything).
    """
    K = active.shape[0]
    free_rank = jnp.cumsum(1.0 - active) * (1.0 - active)
    n_live = jnp.sum(active)
    sel = (active > 0.5) | ((free_rank >= 1.0) & (free_rank <= B - n_live))
    cols = jnp.nonzero(sel, size=B, fill_value=K - 1)[0].astype(jnp.int32)
    min_out = jnp.min(
        jnp.where(sel, K, jnp.arange(K))
    ).astype(jnp.int32)
    return cols, min_out


def a_posterior(
    ZtZ: Array,
    ZtX: Array,
    active: Array,
    sigma_x: Array,
    sigma_a: Array,
) -> tuple[Array, Array]:
    """Posterior of A | Z, X: mean = M Z^T X, per-column covariance sigma_x^2 M.

    Returns (mean (K,D) masked, M (K,K) masked+identity-padded).
    """
    ratio = (sigma_x / sigma_a) ** 2
    W = padded_W(ZtZ, active, ratio)
    M, _ = chol_inv_logdet(W)
    M = M * mask_outer(active)  # zero inactive cross terms for the draw
    mean = (M @ (ZtX * active[:, None])) * active[:, None]
    return mean, M


def a_posterior_draw(
    key: Array,
    ZtZ: Array,
    ZtX: Array,
    active: Array,
    sigma_x: Array,
    sigma_a: Array,
) -> Array:
    """Draw A ~ P(A | Z, X). Columns of A are iid N(mean_d, sigma_x^2 M)."""
    mean, M = a_posterior(ZtZ, ZtX, active, sigma_x, sigma_a)
    K = ZtZ.shape[0]
    D = ZtX.shape[1]
    # chol of sigma_x^2 M with identity padding on inactive block
    Mp = M + jnp.eye(K) * (1.0 - active)
    L = jnp.linalg.cholesky(Mp)
    eps = jax.random.normal(key, (K, D), dtype=ZtX.dtype)
    draw = mean + sigma_x * ((L @ eps) * active[:, None])
    return draw


def uncollapsed_loglik(X: Array, Z: Array, A: Array, sigma_x: Array) -> Array:
    """log N(X | Z A, sigma_x^2 I), summed over all entries."""
    R = X - Z @ A
    n = X.size
    return -0.5 * n * LOG2PI - n * jnp.log(sigma_x) - 0.5 * jnp.sum(R * R) / sigma_x**2


def z_prior_loglik(Z: Array, pi: Array, active: Array) -> Array:
    """sum_k sum_n log Bernoulli(Z_nk | pi_k) over active features."""
    p = jnp.clip(pi, 1e-6, 1.0 - 1e-6)
    ll = Z * jnp.log(p)[None, :] + (1.0 - Z) * jnp.log1p(-p)[None, :]
    return jnp.sum(ll * active[None, :])


def harmonic(N: int) -> float:
    return float(sum(1.0 / i for i in range(1, N + 1)))


def inverse_gamma_draw(key: Array, shape_param: Array, rate_param: Array) -> Array:
    """X ~ InvGamma(a, b) via 1 / Gamma(a, rate=b) (jax gamma is shape-only, scale 1)."""
    g = jax.random.gamma(key, shape_param) / rate_param
    return 1.0 / g


def gamma_draw(key: Array, shape_param: Array, rate_param: Array) -> Array:
    return jax.random.gamma(key, shape_param) / rate_param
