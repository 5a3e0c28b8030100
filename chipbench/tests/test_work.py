"""The work counts against hand counts at the paper's size."""
from chipbench.harness import work

# N = 900 training rows, D = 36, K_max = 32, K_tail = 8, L = 5, P = 5
PAPER = dict(N=900, D=36, K=32, Kt=8, L=5, P=5)


def test_hybrid_iteration_flops_by_hand():
    sweep = 5 * 900 * 6 * 32 * 36                     # 31,104,000
    tail_row = 8 * 64 + 8 * 8 * 36 + 8 * (6 * 36 + 4 * 8) + 2 * 36
    assert tail_row == 4872
    tail = 5 * (180 * 4872 + 2 * 180 * 32 * 36 + 2 * 180 * 64
                + 2 * 180 * 8 * 36)                   # 7,092,000
    master = (2 * 900 * 32 * 32 + 2 * 900 * 32 * 36 + 2 * 32 ** 3
              + 4 * 32 * 32 * 36 + 2 * 900 * 32 * 36 + 2 * 900 * 36)
    assert sweep + tail + master == 44_464_192
    assert work.hybrid_iteration_flops(**PAPER) == 44_464_192


def test_hybrid_iteration_bytes_by_hand():
    # sweeps: X + Z in + Z out per row; tail: X_p, Z, Z_tail in and out;
    # master: statistics and residual read X and Z
    words = 5 * 900 * (36 + 64) + 5 * 180 * (36 + 32 + 16) \
        + 2 * 900 * (36 + 32)
    assert words == 648_000
    assert work.hybrid_iteration_bytes(**PAPER) == 4 * 648_000


def test_score_row_by_hand():
    # K = 8 (the bank's bucket), D = 36, 3 sweeps
    loglik = (2 * 8 * 36 + 2 * 64) + 2 * 8 * 36 + 3 * 8 * 4 * 36 \
        + 2 * 8 * 36 + 3 * 36
    assert loglik == 5420
    assert work.score_row_flops(8, 36, 3, "loglik") == 5420
    assert work.score_row_flops(8, 36, 3, "impute") == 5420 + 576
    assert work.score_row_bytes(8, 36, "loglik") == 4 * 37
    assert work.score_row_bytes(8, 36, "impute") == 4 * 108


def test_dp4_iteration_is_ten_times_the_paper():
    dp4 = work.hybrid_iteration_flops(N=9000, D=36, K=32, Kt=8, L=5, P=4)
    assert dp4 == 460_454_992
