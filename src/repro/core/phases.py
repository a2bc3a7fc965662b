"""The building blocks of the three nvPAX phases (paper section 4.3): the
per-phase warm carry, exact feasibility repair, saturation detection and the
Phase I / Phase II-III step-problem builders.

All of them are trace-safe.  The one three-phase driver that orchestrates
them (priority sweep, saturation rounds) is
:func:`repro.core.batched.solve_three_phase`; the incremental certify pass
(:mod:`repro.core.solver.certify`) and the fleet orchestrator use them too.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import solver
from repro.core.problem import INF, AllocProblem, StepProblem
from repro.core.treeops import sla_matvec, sla_rmatvec, tree_matvec, tree_rmatvec

__all__ = [
    "WarmCarry",
    "merge_warm",
    "repair",
    "saturated_mask",
    "qp_step",
    "lp_step",
]

# Tolerance (watts) for saturation detection, matching the paper's "no
# positive slack" test at control-loop precision.
SAT_TOL = 1e-3
# Max saturation rounds; each round freezes >= 1 device or the loop exits on
# no-progress, so this is a safety net, not a truncation (asserted in tests).
MAX_ROUNDS = 40


class WarmCarry(NamedTuple):
    """Per-phase warm-start carry across control steps.

    Each phase's convex program has a distinct dual geometry (Phase I: QP
    duals on tree/SLA rows; Phases II/III: max-min LP duals including the
    improvement rows), so each phase warm-starts its *duals* from the SAME
    phase's end state at the previous control step, while the primal chains
    through the current step's phases as before.  Carrying the single
    post-Phase-III state into the next Phase I — the previous design — was
    measured to *increase* Phase I iterations on tenant-SLA fleets (LP duals
    poison the QP), whereas the phase-matched carry cuts the max-min rounds'
    iteration counts on drifting telemetry (asserted in
    ``tests/test_engine.py``).

    A pytree of :class:`repro.core.solver.SolverState` leaves, so the same
    carry works for one problem (:func:`repro.core.nvpax.optimize`, the
    engine's :meth:`~repro.core.engine.AllocEngine.step`) and for the
    vmapped batched path (``[K, ...]`` leaves).
    """

    p1: solver.SolverState
    p2: solver.SolverState
    p3: solver.SolverState

    @classmethod
    def zeros(cls, n: int, m: int, k: int, dtype) -> "WarmCarry":
        z = solver.SolverState.zeros(n, m, k, dtype)
        return cls(z, z, z)


def merge_warm(
    chain: solver.SolverState, carry: solver.SolverState | None
) -> solver.SolverState:
    """Phase-matched warm start: primal (and t) chain within the step; duals
    come from the same phase's end state at the previous control step."""
    if carry is None:
        return chain
    return solver.SolverState(
        chain.x, chain.t, carry.y_tree, carry.y_sla, carry.y_imp
    )


# ---------------------------------------------------------------------------
# exact feasibility repair
# ---------------------------------------------------------------------------


@jax.named_scope("repair")
def repair(
    x: jnp.ndarray, ap: AllocProblem, n_depths: int | None = None
) -> jnp.ndarray:
    """Project solver output onto exact feasibility for box + tenant-max +
    tree constraints by monotone scale-downs toward ``l``.

    The solver's prox keeps ``x`` in the box exactly; remaining violations
    are O(solver tolerance) overshoots of aggregate rows.  Scale-downs never
    violate box bounds (caps >= subtree minimums is validated at build) and
    processing tree levels top-down cannot re-violate an ancestor.  Tenant
    *minimums* can in principle lose up to the solver tolerance; tests bound
    this below 1e-6 W.

    Trace-safe: the per-depth sweep is a fixed-trip ``lax.fori_loop``.
    ``n_depths`` (static) must be supplied when ``ap`` holds tracers; it
    defaults to ``ap.n_tree_depths()`` on concrete problems.
    """
    if n_depths is None:
        n_depths = ap.n_tree_depths()
    l = ap.l
    # -- tenant upper bounds --
    if ap.sla.k > 0:
        sums = sla_matvec(x, ap.sla)
        lmin = sla_matvec(l, ap.sla)
        hi = jnp.where(jnp.isfinite(ap.sla.hi), ap.sla.hi, jnp.inf)
        over = sums > hi
        denom = jnp.maximum(sums - lmin, 1e-30)
        fac_t = jnp.where(over, jnp.maximum(hi - lmin, 0.0) / denom, 1.0)
        # per-device factor: min over covering tenants
        fac_dev = jnp.ones_like(x).at[ap.sla.dev].min(fac_t[ap.sla.ten])
        x = l + (x - l) * fac_dev
    # -- tree caps, one level at a time (ranges at equal depth are disjoint) --
    depths = ap.tree.depth
    lmin_node = tree_matvec(l, ap.tree)

    def scale_level(d, x):
        level = depths == d
        sums = tree_matvec(x, ap.tree)
        over = level & (sums > ap.tree.cap)
        denom = jnp.maximum(sums - lmin_node, 1e-30)
        fac_node = jnp.where(
            over, jnp.maximum(ap.tree.cap - lmin_node, 0.0) / denom, 1.0
        )
        # broadcast factors onto (disjoint) ranges: the adjoint's
        # difference-array scatter + prefix sum
        fac_dev = 1.0 + tree_rmatvec(fac_node - 1.0, ap.tree, x.shape[0])
        return l + (x - l) * fac_dev

    x = lax.fori_loop(0, n_depths, scale_level, x)
    return jnp.clip(x, ap.l, ap.u)


# ---------------------------------------------------------------------------
# saturation detection (Algorithm 2, line 5)
# ---------------------------------------------------------------------------


def saturated_mask(
    x: jnp.ndarray, ap: AllocProblem, opt_mask: jnp.ndarray, tol: float = SAT_TOL
) -> jnp.ndarray:
    """Devices in ``opt_mask`` with no positive slack to receive more power:
    at their own upper bound, under a tight PDN node, or in a tenant whose
    upper budget is tight."""
    at_u = ap.u - x <= tol
    tree_slack = ap.tree.cap - tree_matvec(x, ap.tree)
    tight_tree = (tree_slack <= tol).astype(x.dtype)
    under_tight = tree_rmatvec(tight_tree, ap.tree, x.shape[0]) > 0.5
    if ap.sla.k > 0:
        sla_slack = jnp.where(
            jnp.isfinite(ap.sla.hi), ap.sla.hi - sla_matvec(x, ap.sla), jnp.inf
        )
        tight_sla = (sla_slack <= tol).astype(x.dtype)
        in_tight_sla = sla_rmatvec(tight_sla, ap.sla, x.shape[0]) > 0.5
    else:
        in_tight_sla = jnp.zeros_like(at_u)
    return opt_mask & (at_u | under_tight | in_tight_sla)


# ---------------------------------------------------------------------------
# step-problem builders
# ---------------------------------------------------------------------------


def _boxes(ap: AllocProblem, pinned: jnp.ndarray, pin_val: jnp.ndarray):
    lo = jnp.where(pinned, pin_val, ap.l)
    hi = jnp.where(pinned, pin_val, ap.u)
    return lo, hi


def qp_step(
    ap: AllocProblem,
    a_cur: jnp.ndarray,
    mask_a: jnp.ndarray,
    mask_f: jnp.ndarray,
    eps: float,
    pin_free: bool = False,
) -> StepProblem:
    """Phase I level QP (eq. 4): track requests on A, regularize L to l,
    pin F at previously-determined values.

    ``pin_free=True`` applies the paper's simplification for fleets with no
    tenant lower-bound SLAs: devices in L are fixed at ``l`` and the
    eps-regularizer is dropped (section 4.3.1).
    """
    dtype = ap.l.dtype
    mask_l = ~(mask_a | mask_f)
    ws2 = ap.weight_scale**2
    if pin_free:
        w = jnp.where(mask_a, ws2, 0.0)
    else:
        w = jnp.where(mask_a, ws2, jnp.where(mask_l, eps * ws2, 0.0))
    target = jnp.where(mask_a, ap.r, ap.l)
    pinned = mask_f | (mask_l if pin_free else jnp.zeros_like(mask_f))
    pin_val = jnp.where(mask_f, a_cur, ap.l)
    lo, hi = _boxes(ap, pinned, pin_val)
    n = ap.n
    return StepProblem(
        w=w,
        target=target,
        c=jnp.zeros((n,), dtype),
        c_t=jnp.zeros((), dtype),
        lo=lo,
        hi=hi,
        t_lo=jnp.zeros((), dtype),
        t_hi=jnp.zeros((), dtype),
        tree_hi=ap.tree.cap,
        sla_lo=ap.sla.lo,
        sla_hi=ap.sla.hi,
        imp_lo=jnp.full((n,), -INF, dtype),
    )


def lp_step(
    ap: AllocProblem,
    base: jnp.ndarray,
    mask_a: jnp.ndarray,
    mask_f: jnp.ndarray,
    mask_free: jnp.ndarray,
    eps: float,
) -> StepProblem:
    """Phase II/III max-min LP (eqs. 5/6): ``max t + eps*sum_A a - eps*sum_L a``
    with ``a_i - base_i >= t`` on A, F pinned at ``base``."""
    dtype = ap.l.dtype
    n = ap.n
    c = jnp.where(mask_a, -eps, jnp.where(mask_free, eps, 0.0)).astype(dtype)
    lo, hi = _boxes(ap, mask_f, base)
    # max-min raise can never exceed the largest device range
    t_hi = jnp.max(ap.u - ap.l)
    return StepProblem(
        w=jnp.zeros((n,), dtype),
        target=jnp.zeros((n,), dtype),
        c=c,
        c_t=jnp.asarray(-1.0, dtype),
        lo=lo,
        hi=hi,
        t_lo=jnp.zeros((), dtype),
        t_hi=t_hi,
        tree_hi=ap.tree.cap,
        sla_lo=ap.sla.lo,
        sla_hi=ap.sla.hi,
        imp_lo=jnp.where(mask_a, base, -INF).astype(dtype),
    )
