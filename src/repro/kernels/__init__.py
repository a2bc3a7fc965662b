"""Pallas TPU kernels for the system's compute hot spots.

Each kernel package follows the kernel.py (pl.pallas_call + BlockSpec VMEM
tiling) / ops.py (jitted public wrapper) / ref.py (pure-jnp oracle) layout
and is validated in interpret mode on CPU (tests/test_kernels.py):

* ``pdhg_update``  — the paper's hot loop: fused PDHG primal prox /
  extrapolation / dual prox (one VMEM pass vs ~15 elementwise HBM trips);
* ``tree_matvec``  — DFS prefix-sum subtree matvec + adjoint;
* ``flash_attention`` — blocked online-softmax attention for the
  data-plane's 32k-sequence cells (GQA via index-map head folding).

Every wrapper takes ``interpret=None``, which :func:`resolve_interpret`
settles from the backend: on a TPU the kernel compiles for Mosaic (or
raises), elsewhere it runs the traced interpreter.
"""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> interpret everywhere except on a TPU backend."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
