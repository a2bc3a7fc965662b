"""Jitted public wrappers for the fused PDHG update kernel.

``interpret`` defaults to None: the kernel compiles for Mosaic on a TPU
backend and runs the traced interpreter elsewhere
(:func:`repro.kernels.resolve_interpret`).
"""

from __future__ import annotations

from repro.kernels.pdhg_update.kernel import (
    dual_chunk_stats,
    dual_prox,
    primal_chunk_stats,
    primal_update,
)

__all__ = [
    "primal_update",
    "dual_prox",
    "primal_chunk_stats",
    "dual_chunk_stats",
]
