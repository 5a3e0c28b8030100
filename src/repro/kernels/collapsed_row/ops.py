"""Dispatching wrapper for the collapsed_row bit-flip recurrence.

``collapsed_row_flip(..., flavor=...)`` selects the implementation:

* ``"jnp"``    — the pure-jnp oracle (full-K lax.scan, bitwise the seed
  sampler's inner loop). The ``backend="ref"`` sampler uses this.
* ``"packed"`` — the CPU-fast form: O(K) per bit (rss/rH carry) over the
  packed active columns only (dynamic-bound while_loop). The
  ``backend="fast"`` sampler's XLA scan uses this; on a TPU the hybrid
  tail runs the same recurrence inside the fused row-scan kernel
  (``kernels/tail_scan``).

No jit here: the caller (``core/ibp/collapsed.py``) traces this inside an
already-jitted row scan, and ``flavor`` is static by construction.

Occupancy-adaptive packing (DESIGN.md §14): under ``k_live_buckets="on"``
the caller passes the K_live BLOCK (all live columns + the lowest free
slots, canonically ordered) rather than the K_max pad — every flavor is
shape-generic, so K below is whichever width the caller packed to. The
``packed`` flavor additionally accepts the carried ``G = H Hᵀ`` so its
per-bit moves stay O(K) without the per-row O(K²D) GEMM.
"""
from __future__ import annotations

from .fast import collapsed_row_flip_fast
from .ref import collapsed_row_flip_ref

FLAVORS = ("jnp", "packed")


def collapsed_row_flip(
    M, H, x_n, z, v, q, mean, u, m_minus, active_m, N, inv2s2,
    *, flavor: str = "jnp", G=None,
):
    """Run the K-sequential bit-flip recurrence; returns (z, v, q, mean).

    ``G`` (optional) is the caller-carried H Hᵀ; only the ``packed``
    flavor consumes it (the mean-form oracle never materializes G).
    """
    if flavor not in FLAVORS:
        raise ValueError(f"flavor={flavor!r} not in {FLAVORS}")
    if flavor == "packed":
        return collapsed_row_flip_fast(
            M, H, x_n, z, v, q, mean, u, m_minus, active_m, N, inv2s2, G=G
        )
    return collapsed_row_flip_ref(
        M, H, x_n, z, v, q, mean, u, m_minus, active_m, N, inv2s2
    )
