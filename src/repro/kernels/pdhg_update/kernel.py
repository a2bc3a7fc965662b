"""Fused PDHG update — Pallas TPU kernel (preconditioned form).

The solver's hot loop applies ~15 elementwise ops over the primal state per
iteration (prox, extrapolation) and ~8 over each dual block.  Unfused, each
op is an HBM round-trip at fleet scale (n = 1e5-1e6 devices); fused, the
whole update streams x once HBM->VMEM->HBM.  Blocked over n with a VMEM
BlockSpec so arbitrarily large fleets tile cleanly; block size 8*128*8 keeps
the f32 operand tiles + outputs under ~0.5 MB VMEM, lane-aligned (128) and
sublane-aligned (8) for the VPU.

The solver-core overhaul made the step sizes *diagonal* (per-variable
``tau``, per-row ``sigma`` — Pock-Chambolle preconditioning computed from
the tree/SLA incidence), so the kernels take step-size VECTORS streamed
through the same block pipeline as the state; the uniform-step fallback
passes broadcast scalars.

The between-chunk restart/KKT bookkeeping (average accumulation, the
no-progress ``move`` norms, the travel distances to the restart anchors)
used to drop out of the kernels into plain jnp — four extra HBM round-trips
per check.  ``primal_chunk_stats``/``dual_chunk_stats`` fuse them into one
streaming pass each: the updated average accumulator comes out full-size
while every reduction exits as a per-block partial row (max for the move
norms, sum for the squared travel), combined across the tiny ``[n_blocks]``
axis by the caller.

Validated in interpret mode against ``ref.py`` (CPU has no Pallas TPU
lowering); ``interpret=None`` compiles for Mosaic on a TPU backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

__all__ = [
    "primal_update",
    "dual_prox",
    "primal_chunk_stats",
    "dual_chunk_stats",
    "BLOCK",
]

BLOCK = 8 * 128 * 8  # 8192 elements: VPU lane/sublane aligned


def _primal_kernel(
    x_ref, gx_ref, c_ref, w_ref, t_ref, lo_ref, hi_ref, tau_ref, x1_ref, xe_ref
):
    x = x_ref[...]
    tau = tau_ref[...]
    w = w_ref[...]
    num = x - tau * (gx_ref[...] + c_ref[...]) + tau * w * t_ref[...]
    x1 = jnp.clip(num / (1.0 + tau * w), lo_ref[...], hi_ref[...])
    x1_ref[...] = x1
    xe_ref[...] = 2.0 * x1 - x


def _dual_kernel(y_ref, a_ref, sig_ref, lo_ref, hi_ref, out_ref):
    sigma = sig_ref[...]
    z = y_ref[...] + sigma * a_ref[...]
    out_ref[...] = z - sigma * jnp.clip(z / sigma, lo_ref[...], hi_ref[...])


def _pad(v, n_pad, value=0.0):
    return jnp.pad(v, (0, n_pad - v.shape[0]), constant_values=value)


def _as_vec(v, n, dtype):
    """Broadcast a scalar step size to the vector form the kernel streams."""
    v = jnp.asarray(v, dtype)
    return jnp.broadcast_to(v, (n,)) if v.ndim == 0 else v


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def primal_update(x, gx, c, w, target, lo, hi, tau, *, interpret=None, block=BLOCK):
    n = x.shape[0]
    np_ = pl.cdiv(n, block) * block
    args = [_pad(v, np_) for v in (x, gx, c, w, target, lo, hi)]
    # pad with 1.0: the padded lanes have x = lo = hi = 0, so any positive
    # step keeps them inert
    args.append(_pad(_as_vec(tau, n, x.dtype), np_, value=1.0))
    spec = pl.BlockSpec((block,), lambda i: (i,))
    x1, xe = pl.pallas_call(
        _primal_kernel,
        grid=(np_ // block,),
        in_specs=[spec] * 8,
        out_specs=(spec, spec),
        out_shape=(
            jax.ShapeDtypeStruct((np_,), x.dtype),
            jax.ShapeDtypeStruct((np_,), x.dtype),
        ),
        interpret=resolve_interpret(interpret),
    )(*args)
    return x1[:n], xe[:n]


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def dual_prox(y, a, sigma, lo, hi, *, interpret=None, block=BLOCK):
    n = y.shape[0]
    np_ = pl.cdiv(n, block) * block
    big = jnp.asarray(jnp.finfo(y.dtype).max / 2, y.dtype)
    args = [
        _pad(y, np_),
        _pad(a, np_),
        _pad(_as_vec(sigma, n, y.dtype), np_, value=1.0),
        _pad(lo, np_, value=-big),
        _pad(hi, np_, value=big),
    ]
    spec = pl.BlockSpec((block,), lambda i: (i,))
    out = pl.pallas_call(
        _dual_kernel,
        grid=(np_ // block,),
        in_specs=[spec] * 5,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((np_,), y.dtype),
        interpret=resolve_interpret(interpret),
    )(*args)
    return out[:n]


def _primal_stats_kernel(x_ref, px_ref, rx_ref, ax_ref, cnt_ref, axn_ref, part_ref):
    x = x_ref[...]
    axn = ax_ref[...] + x
    axn_ref[...] = axn
    rx = rx_ref[...]
    d_cur = x - rx
    d_avg = axn / cnt_ref[0] - rx
    part_ref[...] = jnp.stack(
        [
            jnp.max(jnp.abs(x - px_ref[...])),
            jnp.max(jnp.abs(x)),
            jnp.sum(d_cur * d_cur),
            jnp.sum(d_avg * d_avg),
        ]
    ).reshape(1, 4)


def _dual_stats_kernel(y_ref, ry_ref, ay_ref, cnt_ref, ayn_ref, part_ref):
    y = y_ref[...]
    ayn = ay_ref[...] + y
    ayn_ref[...] = ayn
    ry = ry_ref[...]
    d_cur = y - ry
    d_avg = ayn / cnt_ref[0] - ry
    part_ref[...] = jnp.stack(
        [jnp.sum(d_cur * d_cur), jnp.sum(d_avg * d_avg), jnp.sum(ry * ry)]
    ).reshape(1, 3)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def primal_chunk_stats(x, px, rx, ax, cnt, *, interpret=None, block=BLOCK):
    """One fused pass over the primal block at a KKT check.

    Returns ``(ax + x, max|x - px|, max|x|, sum (x - rx)^2,
    sum (ax_new/cnt - rx)^2)`` — the average accumulation, the no-progress
    move norms, and the travel distances of the current/average restart
    candidates.  Padded lanes are zero everywhere, so they contribute exact
    zeros to every reduction.
    """
    n = x.shape[0]
    np_ = pl.cdiv(n, block) * block
    nb = np_ // block
    args = [_pad(v, np_) for v in (x, px, rx, ax)]
    args.append(jnp.reshape(jnp.asarray(cnt, x.dtype), (1,)))
    spec = pl.BlockSpec((block,), lambda i: (i,))
    axn, part = pl.pallas_call(
        _primal_stats_kernel,
        grid=(nb,),
        in_specs=[spec] * 4 + [pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=(spec, pl.BlockSpec((1, 4), lambda i: (i, 0))),
        out_shape=(
            jax.ShapeDtypeStruct((np_,), x.dtype),
            jax.ShapeDtypeStruct((nb, 4), x.dtype),
        ),
        interpret=resolve_interpret(interpret),
    )(*args)
    return (
        axn[:n],
        jnp.max(part[:, 0]),
        jnp.max(part[:, 1]),
        jnp.sum(part[:, 2]),
        jnp.sum(part[:, 3]),
    )


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def dual_chunk_stats(y, ry, ay, cnt, *, interpret=None, block=BLOCK):
    """Dual-side twin of :func:`primal_chunk_stats`.

    Returns ``(ay + y, sum (y - ry)^2, sum (ay_new/cnt - ry)^2,
    sum ry^2)`` — the travel distances of the current/average/zero-dual
    restart candidates.
    """
    n = y.shape[0]
    np_ = pl.cdiv(n, block) * block
    nb = np_ // block
    args = [_pad(v, np_) for v in (y, ry, ay)]
    args.append(jnp.reshape(jnp.asarray(cnt, y.dtype), (1,)))
    spec = pl.BlockSpec((block,), lambda i: (i,))
    ayn, part = pl.pallas_call(
        _dual_stats_kernel,
        grid=(nb,),
        in_specs=[spec] * 3 + [pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=(spec, pl.BlockSpec((1, 3), lambda i: (i, 0))),
        out_shape=(
            jax.ShapeDtypeStruct((np_,), y.dtype),
            jax.ShapeDtypeStruct((nb, 3), y.dtype),
        ),
        interpret=resolve_interpret(interpret),
    )(*args)
    return ayn[:n], jnp.sum(part[:, 0]), jnp.sum(part[:, 1]), jnp.sum(part[:, 2])
