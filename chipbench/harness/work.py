"""The work an algorithm needs, counted from its shapes: the same
whatever implements it. FLOPs count a multiply-add as two; bytes count
float32 arrays read and written once per pass.

Hybrid iteration (N rows over P shards of N_p, D columns, K = K_max
slots, Kt = K_tail, L sub-iterations):

* uncollapsed sweep, per row and sub-iteration: the residual
  x - z A (2KD), then per slot a dot r·a_k (2D) and the rank-one
  residual move (2D): 6KD;
* collapsed tail on p', per sub-iteration: its data R = X_p - Z A
  (2 N_p K D) and statistics (2 N_p Kt² + 2 N_p Kt D); per row the
  carried posterior map moved out and back in by rank-one steps
  (8 Kt² + 8 Kt D), per slot the two predictive likelihoods
  (6D + 4Kt), and the birth move's residual (2D);
* master sync: statistics Zᵀ Z and Zᵀ X (2NK² + 2NKD), factor and
  inverse of the K x K system (2K³), the A mean and noise (4K²D), the
  residual sum of squares (2NKD + 2ND).

Scoring one row against one sample (bank width K, n_sweeps sweeps):
the ridge warm start (2KD + 2K²), the residual (2KD), per sweep and
slot a dot and a residual move (4D), the joint log-likelihood
(2KD + 3D); ``impute`` adds its reconstruction (2KD).
"""
from __future__ import annotations

F32 = 4


def hybrid_iteration_flops(N: int, D: int, K: int, Kt: int, L: int,
                           P: int) -> int:
    N_p = N // P
    sweep = L * N * 6 * K * D
    tail_row = 8 * Kt * Kt + 8 * Kt * D + Kt * (6 * D + 4 * Kt) + 2 * D
    tail = L * (N_p * tail_row + 2 * N_p * K * D + 2 * N_p * Kt * Kt
                + 2 * N_p * Kt * D)
    master = (2 * N * K * K + 2 * N * K * D + 2 * K ** 3 + 4 * K * K * D
              + 2 * N * K * D + 2 * N * D)
    return sweep + tail + master


def hybrid_iteration_bytes(N: int, D: int, K: int, Kt: int, L: int,
                           P: int) -> int:
    N_p = N // P
    sweep = L * N * (D + 2 * K)            # X read, Z read and written
    tail = L * N_p * (D + K + 2 * Kt)      # X_p, Z read; Z_tail r/w
    master = 2 * N * (D + K)               # statistics, residual
    return F32 * (sweep + tail + master)


def score_row_flops(K: int, D: int, n_sweeps: int, op: str) -> int:
    per = (2 * K * D + 2 * K * K) + 2 * K * D + n_sweeps * K * 4 * D \
        + 2 * K * D + 3 * D
    if op == "impute":
        per += 2 * K * D
    return per


def score_row_bytes(K: int, D: int, op: str) -> int:
    """Per row and sample: the row (and its mask) in, the answer out."""
    out = D if op == "impute" else 1
    mask = D if op == "impute" else 0
    return F32 * (D + mask + out)
