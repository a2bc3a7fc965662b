"""Matrix-free constraint operators for the nvPAX convex programs.

The constraint matrix ``K`` stacks three row blocks over the primal vector
``z = (x in R^n, t in R)``:

  * ``m`` PDN tree rows: row ``j`` sums devices in the DFS range
    ``[start_j, end_j)`` (coefficient 0 on ``t``);
  * ``k`` tenant SLA rows: row ``k`` sums an arbitrary device subset given
    by a static (device, tenant) incidence edge list (coefficient 0 on
    ``t``);
  * ``n`` max-min improvement rows: row ``i`` is ``x_i - t`` (used by
    Phases II/III; rows are made vacuous via infinite bounds when unused).

Because devices are DFS-ordered, the tree block is a cumulative sum plus two
gathers, and its transpose is a difference-array scatter plus a cumulative
sum — O(n + m) with no sparse data structures.  This is the TPU-native
re-tiling of the paper's constraint handling (DESIGN.md section 2).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "TreeTopo",
    "SlaTopo",
    "prefix_sum",
    "tree_matvec",
    "tree_rmatvec",
    "sla_matvec",
    "sla_rmatvec",
    "full_matvec",
    "full_rmatvec",
]


class TreeTopo(NamedTuple):
    """Static tree-constraint topology (jnp arrays, pytree-compatible)."""

    start: jnp.ndarray  # [m] int32
    end: jnp.ndarray  # [m] int32
    cap: jnp.ndarray  # [m] float
    depth: jnp.ndarray  # [m] int32 (root = 0); used by the feasibility repair

    @property
    def m(self) -> int:
        return self.start.shape[0]


class SlaTopo(NamedTuple):
    """Static tenant-constraint topology.

    ``dev``/``ten`` form an incidence edge list: device ``dev[e]`` belongs
    to tenant ``ten[e]``.  Disjoint tenancy is the common case but is not
    assumed.  ``lo``/``hi`` are aggregate bounds (+-inf when absent).
    """

    dev: jnp.ndarray  # [nnz] int32
    ten: jnp.ndarray  # [nnz] int32
    lo: jnp.ndarray  # [k] float
    hi: jnp.ndarray  # [k] float

    @property
    def k(self) -> int:
        return self.lo.shape[0]

    @classmethod
    def empty(cls, dtype=jnp.float32) -> "SlaTopo":
        return cls(
            dev=jnp.zeros((0,), jnp.int32),
            ten=jnp.zeros((0,), jnp.int32),
            lo=jnp.zeros((0,), dtype),
            hi=jnp.zeros((0,), dtype),
        )


def prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum as a log-depth scan.  ``jnp.cumsum`` lowers to a
    reduce-window that the TPU compiler takes minutes to build in float64
    (124 s for f64[12288] on v5e); the scan builds in under a second."""
    return lax.associative_scan(jnp.add, x)


def tree_matvec(x: jnp.ndarray, tree: TreeTopo) -> jnp.ndarray:
    """Per-node subtree sums of ``x`` — the tree block of ``K z``."""
    csum = jnp.concatenate([jnp.zeros((1,), x.dtype), prefix_sum(x)])
    return csum[tree.end] - csum[tree.start]


def tree_rmatvec(y: jnp.ndarray, tree: TreeTopo, n: int) -> jnp.ndarray:
    """Transpose of :func:`tree_matvec`: device i accumulates its ancestors'
    duals.  Difference-array scatter + prefix sum."""
    diff = jnp.zeros((n + 1,), y.dtype)
    diff = diff.at[tree.start].add(y)
    diff = diff.at[tree.end].add(-y)
    return prefix_sum(diff)[:n]


def sla_matvec(x: jnp.ndarray, sla: SlaTopo) -> jnp.ndarray:
    """Per-tenant sums of ``x`` over the incidence list."""
    if sla.k == 0:
        return jnp.zeros((0,), x.dtype)
    return jax.ops.segment_sum(x[sla.dev], sla.ten, num_segments=sla.k)


def sla_rmatvec(y: jnp.ndarray, sla: SlaTopo, n: int) -> jnp.ndarray:
    if sla.k == 0:
        return jnp.zeros((n,), y.dtype)
    return jnp.zeros((n,), y.dtype).at[sla.dev].add(y[sla.ten])


def full_matvec(
    x: jnp.ndarray, t: jnp.ndarray, tree: TreeTopo, sla: SlaTopo
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``K z`` split into (tree rows, tenant rows, improvement rows)."""
    return tree_matvec(x, tree), sla_matvec(x, sla), x - t


def full_rmatvec(
    y_tree: jnp.ndarray,
    y_sla: jnp.ndarray,
    y_imp: jnp.ndarray,
    tree: TreeTopo,
    sla: SlaTopo,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``K^T y`` -> (gradient on x, gradient on t)."""
    n = y_imp.shape[0]
    gx = tree_rmatvec(y_tree, tree, n) + sla_rmatvec(y_sla, sla, n) + y_imp
    gt = -jnp.sum(y_imp)
    return gx, gt
