"""Jitted public wrappers for the tree/segment matvec kernels (interpreted
off the TPU; see :func:`repro.kernels.resolve_interpret`)."""

from __future__ import annotations

from repro.kernels.tree_matvec.kernel import (
    sla_matvec,
    sla_rmatvec,
    tree_matvec,
    tree_rmatvec,
)

__all__ = ["sla_matvec", "sla_rmatvec", "tree_matvec", "tree_rmatvec"]
