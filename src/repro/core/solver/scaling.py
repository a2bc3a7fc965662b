"""Diagonal scaling and preconditioning for the matrix-free PDHG solver.

Two layers, both computed in closed form from the tree/SLA incidence (prefix
sums + segment sums — never a sparse matrix):

1. **Metric scaling** (:func:`make_scales`): curvature-aware primal variable
   scales (``s_i = 1/sqrt(w_i)`` so every quadratic variable has unit
   curvature; problem-range scale for LP variables), analytic row
   equilibration, and the fold-out of pinned columns.  This is the change
   of variables the solve runs in; it is what makes the mixed
   ``w in {1, eps, 0}`` Phase I QP converge instead of stalling on the eps
   block, and it is unchanged by the solver-core overhaul.

2. **Step-size preconditioning** (:func:`pc_step_sizes`): per-variable /
   per-row Pock-Chambolle step sizes for the *scaled* operator
   ``A = D K_mov S``:

       tau_j   = theta * omega / sum_i |A_ij|      (column absolute sums)
       sigma_i = theta / (omega * sum_j |A_ij|)    (row absolute sums)

   which satisfy ``||Sigma^(1/2) A T^(1/2)|| <= theta`` for every
   ``theta <= 1`` *by construction* — no global operator-norm estimate.
   The pre-overhaul scalar steps (``tau = theta*omega/||A||`` with ``||A||``
   from a power iteration) remain available via
   ``SolverOptions(precondition=False)``; on degenerate fleet geometries the
   power estimate is exact yet the uniform step still certifies an order of
   magnitude slower than the diagonal one (see tests/test_solver_degenerate).

   Vacuous improvement rows (``imp_lo = -inf`` — every Phase I row) carry
   zero dual by construction, so they are excluded from the column sums:
   charging every device for a row that cannot act would halve the Phase I
   step sizes for nothing.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.problem import StepProblem
from repro.core.treeops import (
    SlaTopo,
    TreeTopo,
    sla_matvec,
    sla_rmatvec,
    tree_matvec,
    tree_rmatvec,
)

__all__ = [
    "Scales",
    "StepSizes",
    "make_scales",
    "pc_step_sizes",
    "uniform_step_sizes",
    "scaled_matvec",
    "scaled_rmatvec",
    "estimate_norm",
]


class Scales(NamedTuple):
    s: jnp.ndarray  # [n] primal variable scales
    s_t: jnp.ndarray  # scalar: scale of t
    mov: jnp.ndarray  # [n] 1.0 where the variable can move (lo < hi)
    t_mov: jnp.ndarray  # scalar 0/1
    d_tree: jnp.ndarray  # [m] row scales
    d_sla: jnp.ndarray  # [k]
    d_imp: jnp.ndarray  # [n]


class StepSizes(NamedTuple):
    """Unit-primal-weight diagonal step sizes for the scaled operator.

    The loop multiplies ``tau_*`` by the current primal weight ``omega`` and
    divides ``sig_*`` by it; the products ``tau_j * sig_i`` are
    omega-invariant, so the Pock-Chambolle bound holds for every omega.
    """

    tau_x: jnp.ndarray  # [n]
    tau_t: jnp.ndarray  # scalar
    sig_tree: jnp.ndarray  # [m]
    sig_sla: jnp.ndarray  # [k]
    sig_imp: jnp.ndarray  # [n]


def make_scales(prob: StepProblem, tree: TreeTopo, sla: SlaTopo) -> Scales:
    """Curvature-aware primal scales + analytic row equilibration.

    ``s_i = 1/sqrt(w_i)`` gives every quadratic variable unit curvature in
    the scaled metric; zero-curvature (LP) variables use the problem's
    power-range scale so primal travel distances are O(1).

    Pinned variables (``lo == hi`` — finalized priority levels, saturated
    devices, the idle fleet in Phase I) are *folded out of the operator
    entirely*: their contribution to every constraint row is a constant that
    the caller moves into the row bounds, and their columns are zeroed via
    ``mov``.  Without this the operator norm (and therefore the step sizes)
    is dominated by columns that cannot move — observed as a frozen solver
    on the 12k-device fleet where ~90% of variables are pinned in Phase I.

    Row norms of the scaled movable constraint matrix are subtree / tenant
    sums of ``s^2 * mov`` — computable with the same prefix/segment-sum
    machinery as the matvec itself.
    """
    dtype = prob.lo.dtype
    rng = jnp.where(jnp.isfinite(prob.hi - prob.lo), prob.hi - prob.lo, 0.0)
    range_scale = jnp.maximum(jnp.max(rng), 1.0)
    s = jnp.where(prob.w > 0, 1.0 / jnp.sqrt(jnp.maximum(prob.w, 1e-30)), range_scale)
    s = jnp.minimum(s, range_scale * 1e3)  # cap pathological 1/sqrt(w)
    # t appears in every active improvement row, giving it a dense column of
    # norm ~sqrt(n_imp) that would cap everyone's step size; shrink its scale
    # by 1/sqrt(n_imp) so the scaled column norm is O(1).
    n_imp = jnp.sum(jnp.isfinite(prob.imp_lo).astype(dtype))
    s_t = (range_scale / jnp.sqrt(jnp.maximum(n_imp, 1.0))).astype(dtype)

    mov = (prob.hi - prob.lo > 0).astype(dtype)
    t_mov = (prob.t_hi - prob.t_lo > 0).astype(dtype)
    s2m = s * s * mov
    tree_norm2 = tree_matvec(s2m, tree)
    d_tree = lax.rsqrt(jnp.maximum(tree_norm2, 1.0))
    if sla.k > 0:
        sla_norm2 = jax.ops.segment_sum(s2m[sla.dev], sla.ten, num_segments=sla.k)
        d_sla = lax.rsqrt(jnp.maximum(sla_norm2, 1.0))
    else:
        d_sla = jnp.zeros((0,), dtype)
    d_imp = lax.rsqrt(jnp.maximum(s2m + s_t * s_t * t_mov, 1.0))
    return Scales(s, s_t, mov, t_mov, d_tree, d_sla, d_imp)


def scaled_matvec(xs, ts, tree, sla, sc: Scales, *, use_kernels=False):
    """Scaled forward operator D2 K_mov S, split by row block.  Input is the
    SCALED primal (x~, t~); pinned columns are zeroed (folded into bounds).

    ``use_kernels`` routes the tree prefix / SLA segment reductions through
    the chunked Pallas kernels (:mod:`repro.kernels.tree_matvec`) instead of
    the plain jnp ops — the ``SolverOptions.use_pallas_tree`` path.
    """
    x = sc.s * sc.mov * xs
    if use_kernels:
        from repro.kernels import tree_matvec as tk

        kx = tk.tree_matvec(x, tree.start, tree.end)
        sx = tk.sla_matvec(x, sla.dev, sla.ten, sla.k) if sla.k else sla_matvec(x, sla)
    else:
        kx = tree_matvec(x, tree)
        sx = sla_matvec(x, sla)
    return (
        sc.d_tree * kx,
        sc.d_sla * sx,
        sc.d_imp * (x - sc.s_t * sc.t_mov * ts),
    )


def scaled_rmatvec(
    y_tree, y_sla, y_imp, tree, sla, sc: Scales, n, *, use_kernels=False
):
    """Scaled adjoint S K_mov^T D2 -> (grad on x~, grad on t~)."""
    yi = sc.d_imp * y_imp
    if use_kernels:
        from repro.kernels import tree_matvec as tk

        gx = tk.tree_rmatvec(sc.d_tree * y_tree, tree.start, tree.end, n)
        if sla.k:
            gx = gx + tk.sla_rmatvec(sc.d_sla * y_sla, sla.dev, sla.ten, n)
        gx = gx + yi
    else:
        gx = (
            tree_rmatvec(sc.d_tree * y_tree, tree, n)
            + sla_rmatvec(sc.d_sla * y_sla, sla, n)
            + yi
        )
    gt = -sc.s_t * sc.t_mov * jnp.sum(yi)
    return sc.s * sc.mov * gx, gt


def pc_step_sizes(
    prob: StepProblem, tree: TreeTopo, sla: SlaTopo, sc: Scales, theta
) -> StepSizes:
    """Pock-Chambolle (alpha = 1) diagonal step sizes from the incidence.

    Absolute row/column sums of the scaled movable operator are the same
    structured reductions as the matvec itself: subtree prefix sums for the
    tree block, segment sums for the SLA block, an ancestor-scatter
    (``tree_rmatvec``) for the per-device column sums.
    """
    n = prob.n
    dtype = prob.lo.dtype
    sm = sc.s * sc.mov  # per-variable |column entry| before row scaling
    act = jnp.isfinite(prob.imp_lo).astype(dtype)  # improvement row is live

    # row absolute sums of A = D K_mov S
    row_tree = sc.d_tree * tree_matvec(sm, tree)
    if sla.k > 0:
        row_sla = sc.d_sla * jax.ops.segment_sum(
            sm[sla.dev], sla.ten, num_segments=sla.k
        )
    else:
        row_sla = jnp.zeros((0,), dtype)
    row_imp = sc.d_imp * (sm + sc.s_t * sc.t_mov)

    # column absolute sums: each device accumulates its covering rows' scales
    col_x = sm * (
        tree_rmatvec(sc.d_tree, tree, n)
        + sla_rmatvec(sc.d_sla, sla, n)
        + sc.d_imp * act
    )
    col_t = sc.s_t * sc.t_mov * jnp.sum(sc.d_imp * act)

    tiny = jnp.asarray(1e-12, dtype)
    theta = jnp.asarray(theta, dtype)
    return StepSizes(
        tau_x=theta / jnp.maximum(col_x, tiny),
        tau_t=theta / jnp.maximum(col_t, tiny),
        sig_tree=theta / jnp.maximum(row_tree, tiny),
        sig_sla=theta / jnp.maximum(row_sla, tiny),
        sig_imp=theta / jnp.maximum(row_imp, tiny),
    )


def uniform_step_sizes(
    tree: TreeTopo, sla: SlaTopo, sc: Scales, n: int, theta, power_iters: int, dtype
) -> StepSizes:
    """Pre-overhaul scalar steps broadcast to the diagonal form:
    ``tau = sigma = theta / ||A||`` with the norm from a power iteration."""
    knorm = jnp.maximum(estimate_norm(tree, sla, sc, n, power_iters, dtype), 1e-6)
    tau = jnp.asarray(theta, dtype) / knorm
    return StepSizes(
        tau_x=jnp.full((n,), tau, dtype),
        tau_t=tau.astype(dtype),
        sig_tree=jnp.full((tree.m,), tau, dtype),
        sig_sla=jnp.full((sla.k,), tau, dtype),
        sig_imp=jnp.full((n,), tau, dtype),
    )


def estimate_norm(tree, sla, sc: Scales, n, iters, dtype):
    """||D2 K S||_2 via power iteration on (D2 K S)^T (D2 K S)."""

    def body(_, v):
        x, t = v
        nrm = jnp.sqrt(jnp.sum(x * x) + t * t)
        x, t = x / nrm, t / nrm
        a, b, c = scaled_matvec(x, t, tree, sla, sc)
        return scaled_rmatvec(a, b, c, tree, sla, sc, n)

    x0 = jnp.ones((n,), dtype) / jnp.sqrt(jnp.asarray(n + 1, dtype))
    t0 = jnp.ones((), dtype) / jnp.sqrt(jnp.asarray(n + 1, dtype))
    x, t = lax.fori_loop(0, iters, body, (x0, t0))
    return jnp.sqrt(jnp.sqrt(jnp.sum(x * x) + t * t))  # sqrt of ||K^TK v|| ~ ||K||
