"""Tree-constraint + tenant-segment matvecs — chunked Pallas TPU kernels.

DFS device ordering turns every PDN subtree-sum row into a prefix-sum
difference (DESIGN.md section 2): ``K x = csum[end] - csum[start]``.  The
original kernel kept the whole device vector in one VMEM block; at fleet
scale (n = 1e5-1e6+) that busts the 16 MB budget, so everything here is
*chunked over a 1-D grid*:

* **prefix sum** — two passes: pass 1 computes each block's local inclusive
  cumsum plus its total; a tiny exclusive cumsum of the [n_blocks] totals
  (plain jnp — it is O(n/BLOCK) elements) produces per-block offsets; pass 2
  adds each block's offset.  Sequential-grid carry without any cross-block
  VMEM traffic.
* **endpoint gather / difference-array scatter** — blocked over the row
  axis.  The scatter accumulates into a *revisited* output block (the TPU
  grid is sequential, so zero-on-first-visit + ``out += part`` per block is
  the canonical accumulation pattern), followed by the blocked prefix sum.
* **tenant segment ops** (``sla_matvec``/``sla_rmatvec``) — the tenant
  incidence edge list is blocked; each block gathers its device (resp.
  tenant-dual) values and segment-adds into the revisited [k]- (resp.
  [n]-) sized accumulator.  Padded edges land in an inert extra slot that
  is dropped on return.

Validated in interpret mode against ``ref.py`` (CPU has no Pallas TPU
lowering); ``interpret=None`` compiles for Mosaic on a TPU backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

__all__ = ["tree_matvec", "tree_rmatvec", "sla_matvec", "sla_rmatvec", "BLOCK"]

BLOCK = 64 * 1024


def _pad_to(v, size, value=0):
    return jnp.pad(v, (0, size - v.shape[0]), constant_values=value)


def _local_prefix_kernel(x_ref, out_ref, tot_ref):
    c = jnp.cumsum(x_ref[...])
    out_ref[...] = c
    tot_ref[...] = c[-1:]


def _add_offset_kernel(c_ref, off_ref, out_ref):
    out_ref[...] = c_ref[...] + off_ref[pl.program_id(0)]


def _blocked_prefix(x, *, interpret, block):
    """Inclusive prefix sum chunked over the grid (see module docstring).
    Returns the padded-length prefix vector."""
    n = x.shape[0]
    np_ = pl.cdiv(n, block) * block
    nb = np_ // block
    spec = pl.BlockSpec((block,), lambda i: (i,))
    local, tot = pl.pallas_call(
        _local_prefix_kernel,
        grid=(nb,),
        in_specs=[spec],
        out_specs=(spec, pl.BlockSpec((1,), lambda i: (i,))),
        out_shape=(
            jax.ShapeDtypeStruct((np_,), x.dtype),
            jax.ShapeDtypeStruct((nb,), x.dtype),
        ),
        interpret=resolve_interpret(interpret),
    )(_pad_to(x, np_))
    off = jnp.concatenate([jnp.zeros((1,), x.dtype), jnp.cumsum(tot)])[:nb]
    return pl.pallas_call(
        _add_offset_kernel,
        grid=(nb,),
        in_specs=[spec, pl.BlockSpec((nb,), lambda i: (0,))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((np_,), x.dtype),
        interpret=resolve_interpret(interpret),
    )(local, off)


def _gather_kernel(csum_ref, start_ref, end_ref, out_ref):
    s = start_ref[...]
    e = end_ref[...]
    cs = csum_ref[...]
    lo = jnp.where(s > 0, jnp.take(cs, jnp.maximum(s - 1, 0)), 0.0)
    out_ref[...] = jnp.take(cs, e - 1) - lo


def _scatter_diff_kernel(y_ref, start_ref, end_ref, diff_ref):
    @pl.when(pl.program_id(0) == 0)
    def _zero():
        diff_ref[...] = jnp.zeros_like(diff_ref)

    y = y_ref[...]
    acc = jnp.zeros((diff_ref.shape[0],), y.dtype)
    acc = acc.at[start_ref[...]].add(y)
    acc = acc.at[end_ref[...]].add(-y)
    diff_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("interpret", "block", "row_block"))
def tree_matvec(x, start, end, *, interpret=None, block=BLOCK, row_block=BLOCK):
    """out[j] = sum x[start_j:end_j], chunked over devices and rows.

    Padded rows use the empty range [n, n) so they contribute exact zeros.
    """
    n = x.shape[0]
    m = start.shape[0]
    csum = _blocked_prefix(x, interpret=interpret, block=block)[:n]
    mp = pl.cdiv(m, row_block) * row_block
    mb = mp // row_block
    rspec = pl.BlockSpec((row_block,), lambda i: (i,))
    out = pl.pallas_call(
        _gather_kernel,
        grid=(mb,),
        in_specs=[pl.BlockSpec((n,), lambda i: (0,)), rspec, rspec],
        out_specs=rspec,
        out_shape=jax.ShapeDtypeStruct((mp,), x.dtype),
        interpret=resolve_interpret(interpret),
    )(csum, _pad_to(start, mp, value=n), _pad_to(end, mp, value=n))
    return out[:m]


@functools.partial(jax.jit, static_argnames=("n", "interpret", "block", "row_block"))
def tree_rmatvec(y, start, end, n, *, interpret=None, block=BLOCK, row_block=BLOCK):
    """Adjoint via blocked difference-array scatter + blocked prefix sum."""
    m = y.shape[0]
    mp = pl.cdiv(m, row_block) * row_block
    mb = mp // row_block
    rspec = pl.BlockSpec((row_block,), lambda i: (i,))
    diff = pl.pallas_call(
        _scatter_diff_kernel,
        grid=(mb,),
        in_specs=[rspec, rspec, rspec],
        out_specs=pl.BlockSpec((n + 1,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((n + 1,), y.dtype),
        interpret=resolve_interpret(interpret),
    )(_pad_to(y, mp), _pad_to(start, mp), _pad_to(end, mp))
    return _blocked_prefix(diff, interpret=interpret, block=block)[:n]


def _sla_matvec_kernel(x_ref, dev_ref, ten_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    xv = jnp.take(x_ref[...], dev_ref[...])
    acc = jnp.zeros((out_ref.shape[0],), xv.dtype)
    out_ref[...] += acc.at[ten_ref[...]].add(xv)


def _sla_rmatvec_kernel(y_ref, dev_ref, ten_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    yv = jnp.take(y_ref[...], ten_ref[...])
    acc = jnp.zeros((out_ref.shape[0],), yv.dtype)
    out_ref[...] += acc.at[dev_ref[...]].add(yv)


@functools.partial(jax.jit, static_argnames=("k", "interpret", "edge_block"))
def sla_matvec(x, dev, ten, k, *, interpret=None, edge_block=BLOCK):
    """Per-tenant sums over the incidence edge list, chunked over edges:
    out[t] = sum_{e: ten_e = t} x[dev_e]."""
    e = dev.shape[0]
    if e == 0:
        return jnp.zeros((k,), x.dtype)
    ep = pl.cdiv(e, edge_block) * edge_block
    eb = ep // edge_block
    espec = pl.BlockSpec((edge_block,), lambda i: (i,))
    out = pl.pallas_call(
        _sla_matvec_kernel,
        grid=(eb,),
        in_specs=[pl.BlockSpec((x.shape[0],), lambda i: (0,)), espec, espec],
        out_specs=pl.BlockSpec((k + 1,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((k + 1,), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x, _pad_to(dev, ep), _pad_to(ten, ep, value=k))
    return out[:k]


@functools.partial(jax.jit, static_argnames=("n", "interpret", "edge_block"))
def sla_rmatvec(y, dev, ten, n, *, interpret=None, edge_block=BLOCK):
    """Adjoint: device d accumulates its tenants' duals, chunked over edges.
    Padded edges read an inert zero dual and scatter to an inert slot."""
    e = dev.shape[0]
    if e == 0:
        return jnp.zeros((n,), y.dtype)
    k = y.shape[0]
    ep = pl.cdiv(e, edge_block) * edge_block
    eb = ep // edge_block
    espec = pl.BlockSpec((edge_block,), lambda i: (i,))
    y_ext = jnp.concatenate([y, jnp.zeros((1,), y.dtype)])
    out = pl.pallas_call(
        _sla_rmatvec_kernel,
        grid=(eb,),
        in_specs=[pl.BlockSpec((k + 1,), lambda i: (0,)), espec, espec],
        out_specs=pl.BlockSpec((n + 1,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((n + 1,), y.dtype),
        interpret=resolve_interpret(interpret),
    )(y_ext, _pad_to(dev, ep, value=n), _pad_to(ten, ep, value=k))
    return out[:n]
