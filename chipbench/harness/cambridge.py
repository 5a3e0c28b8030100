"""The Griffiths–Ghahramani "Cambridge" data (Griffiths & Ghahramani
2011): four fixed binary 6x6 images; each row switches each feature on
with probability 1/2 and adds isotropic Gaussian noise,

    X = Z A_true + eps,  eps ~ N(0, sigma_n^2),  X in R^{N x 36}.

A copy kept with the benchmark, so that the data a cell runs on cannot
move with the program; ``tests/test_harness.py`` pins its output.
"""
from __future__ import annotations

import numpy as np

_BARS = (
    ((0, 0), (0, 1), (0, 2), (1, 0), (1, 2), (2, 0), (2, 1), (2, 2)),
    ((0, 3), (0, 4), (0, 5), (1, 3), (1, 4), (2, 3)),
    ((3, 0), (4, 0), (4, 1), (5, 0), (5, 1), (5, 2)),
    ((3, 4), (3, 5), (4, 3), (4, 4), (4, 5), (5, 4), (5, 5)),
)


def features() -> np.ndarray:
    """(4, 36) float32: the four images, flattened row by row."""
    A = np.zeros((4, 6, 6), np.float32)
    for k, cells in enumerate(_BARS):
        for r, c in cells:
            A[k, r, c] = 1.0
    return A.reshape(4, 36)


def cambridge(N: int, sigma_n: float, seed: int, p_feature: float = 0.5):
    """(X (N, 36), Z_true (N, 4)) float32 from ``seed``."""
    rng = np.random.default_rng(seed)
    Z = (rng.random((N, 4)) < p_feature).astype(np.float32)
    X = Z @ features() + sigma_n * rng.standard_normal((N, 36)).astype(
        np.float32)
    return X.astype(np.float32), Z


def train_eval_split(X: np.ndarray, eval_frac: float, seed: int):
    """(train, eval) rows by a permutation drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(X.shape[0])
    n_eval = int(round(X.shape[0] * eval_frac))
    return X[perm[n_eval:]], X[perm[:n_eval]]
