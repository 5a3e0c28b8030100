"""The numbers that decide ``correct``: each answer of a run against the
float64 reference, given the same inputs and random numbers.

Fit cells, per checked iteration (state in -> state out):

``z_off``       share of the N x K_max bits of Z out that differ from
                the reference's iteration from the same state in. Covers
                the uncollapsed sweeps, the collapsed tail on p', the
                promotion of its births and the deaths.
``master_gap``  the largest gap of the master sync's draws: A (largest
                entry gap over largest entry), pi (absolute), sigma_x,
                sigma_a, alpha (relative), each drawn by the reference
                from the run's own Z out; 1 where p', the iteration
                count or the next key differ.

Serve cells, per checked request (rows in -> answer out):

``gap_p50``     median over checked answers of the answer's largest
                entry gap |served - ref| / (1 + |ref|): the arithmetic.
``off_share``   share of checked answers whose gap passes ``OFF_GAP``:
                decisions that went the other way, wrong or missing rows.
"""
from __future__ import annotations

import numpy as np

from . import ibp
from .arith import Arith
from .variates import fit_variates, master_variates

OFF_GAP = 1e-3


def replay(mode: str, Xs, s_in: dict, hyp: dict, L: int, Kt: int,
           P_sync: int) -> dict:
    """The reference's own iteration from ``s_in`` at precision ``mode``:
    a full state out, in the layout of ``s_in``."""
    ar = Arith(mode)
    P, N_p, K = s_in["Z"].shape
    N, D = P * N_p, Xs.shape[-1]
    v = fit_variates(s_in["key"], int(s_in["p_prime"]), s_in["alpha"], N,
                     P=P, L=L, N_p=N_p, K=K, Kt=Kt)
    Z, act = ibp.iteration_z(ar, Xs, s_in["Z"], s_in["A"], s_in["pi"],
                             s_in["active"], s_in["alpha"], s_in["sigma_x"],
                             s_in["sigma_a"], int(s_in["p_prime"]), L, v)
    mv = master_variates(s_in["key"], Z, act, N, dict(hyp, D=D), P=P_sync)
    out = ibp.master(ar, Xs, Z, act, s_in["sigma_x"], s_in["sigma_a"], mv,
                     hyp, N, D)
    return dict(out, Z=Z, active=act, p_prime=int(mv["p_prime"]),
                it=int(s_in["it"]) + 1, key_data=np.asarray(mv["next_key"]))


def fit_numbers(Xs, s_in: dict, s_out: dict, hyp: dict, L: int, Kt: int,
                P_sync: int) -> dict:
    """``z_off`` and ``master_gap`` of one iteration ``s_in -> s_out``."""
    ar = Arith("f64")
    P, N_p, K = s_in["Z"].shape
    N, D = P * N_p, Xs.shape[-1]
    v = fit_variates(s_in["key"], int(s_in["p_prime"]), s_in["alpha"], N,
                     P=P, L=L, N_p=N_p, K=K, Kt=Kt)
    Z_ref, _ = ibp.iteration_z(
        ar, Xs, s_in["Z"], s_in["A"], s_in["pi"], s_in["active"],
        s_in["alpha"], s_in["sigma_x"], s_in["sigma_a"],
        int(s_in["p_prime"]), L, v)
    z_off = float(np.mean(Z_ref != np.asarray(s_out["Z"], np.float64)))

    mv = master_variates(s_in["key"], s_out["Z"], s_out["active"], N,
                         dict(hyp, D=D), P=P_sync)
    tf = ibp.master(ar, Xs, s_out["Z"], s_out["active"], s_in["sigma_x"],
                    s_in["sigma_a"], mv, hyp, N, D, A_noise=s_out["A"])

    def rel(name):
        ref = float(tf[name])
        return abs(float(s_out[name]) - ref) / abs(ref)

    A_out = np.asarray(s_out["A"], np.float64)
    gaps = [
        float(np.max(np.abs(A_out - tf["A"]))
              / max(float(np.max(np.abs(tf["A"]))), 1e-30)),
        float(np.max(np.abs(np.asarray(s_out["pi"], np.float64) - tf["pi"]))),
        rel("sigma_x"), rel("sigma_a"), rel("alpha"),
        float(int(s_out["p_prime"]) != int(mv["p_prime"])),
        float(int(s_out["it"]) != int(s_in["it"]) + 1),
        float(not np.array_equal(np.asarray(s_out["key_data"]),
                                 np.asarray(mv["next_key"]))),
    ]
    return {"z_off": z_off, "master_gap": max(gaps)}


def answer_gap(served, ref) -> float:
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    if served.shape != ref.shape or not np.all(np.isfinite(served)):
        return float("inf")
    if served.size == 0:
        return 0.0
    return float(np.max(np.abs(served - ref) / (1.0 + np.abs(ref))))


def serve_numbers(gaps: list[float]) -> dict:
    g = np.asarray(gaps, np.float64)
    return {"gap_p50": float(np.median(g)),
            "off_share": float(np.mean(g > OFF_GAP))}
