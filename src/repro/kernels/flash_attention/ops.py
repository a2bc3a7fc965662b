"""Jitted public wrapper for the flash-attention kernel (interpreted off the
TPU; see :func:`repro.kernels.resolve_interpret`)."""

from __future__ import annotations

from repro.kernels.flash_attention.kernel import flash_attention

__all__ = ["flash_attention"]
