"""Arithmetic of the plain reference at a stated precision.

``f64``    float64 throughout: the reference every run is compared with.
``f32``    float32 with exact float32 products (a TPU's ``"highest"``).
``bf16x3`` float32 elementwise, products as three bfloat16 passes
           (a TPU's ``"high"``): the control, one step below ``f32``.
``bf16``   float32 elementwise, products as one bfloat16 pass (a TPU's
           default for a float32 product).

The bfloat16 passes are emulated in NumPy (split each factor into a
bfloat16 head and tail, multiply exactly, accumulate in float32), so a
mode gives the same numbers on any host.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

MODES = ("f64", "f32", "bf16x3", "bf16")


def _bf16(x: np.ndarray) -> np.ndarray:
    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


class Arith:
    def __init__(self, mode: str):
        if mode not in MODES:
            raise ValueError(f"mode={mode!r} not in {MODES}")
        self.mode = mode
        self.dt = np.float64 if mode == "f64" else np.float32

    def a(self, x) -> np.ndarray:
        return np.asarray(x, self.dt)

    def mm(self, a, b) -> np.ndarray:
        a, b = self.a(a), self.a(b)
        if self.mode in ("f64", "f32"):
            return a @ b
        ah, bh = _bf16(a), _bf16(b)
        if self.mode == "bf16":
            return ah @ bh
        al, bl = _bf16(a - ah), _bf16(b - bh)
        return ah @ bh + (ah @ bl + al @ bh)

    def chol_inv(self, W: np.ndarray) -> np.ndarray:
        """W⁻¹ through the Cholesky factor, W symmetric positive definite."""
        L = np.linalg.cholesky(self.a(W))
        Linv = np.linalg.solve(L, np.eye(W.shape[0], dtype=self.dt))
        return self.mm(Linv.T, Linv)

    def logit(self, p, eps: float) -> np.ndarray:
        p = np.clip(self.a(p), eps, 1.0 - eps)
        return np.log(p) - np.log1p(-p)
