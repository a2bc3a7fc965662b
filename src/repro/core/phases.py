"""The three nvPAX phases (paper section 4.3) + feasibility repair and
saturation detection.

Orchestration is host-level Python (priority sweep, saturation rounds); the
inner convex solves are the single jitted program of :mod:`repro.core.solver`,
warm-started across rounds.  A fully-jitted variant for batched/vmapped
evaluation lives in :mod:`repro.core.batched`.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import solver
from repro.core.problem import INF, AllocProblem, StepProblem
from repro.core.treeops import sla_matvec, sla_rmatvec, tree_matvec, tree_rmatvec
from repro.core.waterfill import tree_project_jax

__all__ = [
    "PhaseStats",
    "WarmCarry",
    "merge_warm",
    "repair",
    "saturated_mask",
    "phase1",
    "run_maxmin_phase",
]

# Tolerance (watts) for saturation detection, matching the paper's "no
# positive slack" test at control-loop precision.
SAT_TOL = 1e-3
# Max saturation rounds; each round freezes >= 1 device or the loop exits on
# no-progress, so this is a safety net, not a truncation (asserted in tests).
MAX_ROUNDS = 40


class PhaseStats(NamedTuple):
    solves: int
    iterations: int
    converged: bool
    max_primal_res: float
    # every inner solve exited KKT-certified (False when any solve exited on
    # the no-progress/optimal-vertex certificate — see solver.termination)
    kkt_certified: bool = True


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
    carry works for the host driver (:func:`repro.core.nvpax.optimize`), the
    fully-jitted engine, and the vmapped batched path (``[K, ...]`` leaves).
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

    Trace-safe: the per-depth sweep is a fixed-trip ``lax.fori_loop``, so
    the same code serves the host drivers and the fully-jitted batched
    engine.  ``n_depths`` (static) must be supplied when ``ap`` holds
    tracers; it defaults to ``ap.n_tree_depths()`` on concrete problems.
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


# ---------------------------------------------------------------------------
# phase drivers
# ---------------------------------------------------------------------------


_tree_project = jax.jit(tree_project_jax, static_argnames="n_depths")


def phase1(
    ap: AllocProblem,
    opts: solver.SolverOptions,
    eps: float = 1e-5,
    warm: solver.SolverState | None = None,
    use_waterfill: bool = True,
) -> tuple[jnp.ndarray, solver.SolverState, PhaseStats]:
    """Algorithm 1: priority-ordered request satisfaction.

    When no tenant SLAs are present (``use_waterfill=True``) each level QP
    is a weighted projection onto the box and the tree's caps, solved
    exactly by :func:`repro.core.waterfill.tree_project_jax`; the solver
    state passes through untouched.  With SLAs the PDHG solve is required.
    """
    n, m, k = ap.n, ap.tree.m, ap.sla.k
    dtype = ap.l.dtype
    state = warm if warm is not None else solver.SolverState.zeros(n, m, k, dtype)
    x = ap.l
    finalized = jnp.zeros((n,), bool)
    # Sweep order and the pin-free simplification (paper 4.3.1) come from the
    # problem's precomputed level metadata — the same metadata that
    # parameterizes the fully-jitted engine in repro.core.batched, so the
    # host and jitted paths cannot drift.
    levels = ap.priority_levels(active_only=True)
    pin_free = ap.pin_free_ok()
    n_depths = ap.n_tree_depths()
    solves = iters = 0
    conv = cert = True
    maxres = 0.0
    project = use_waterfill and k == 0
    for p in levels:
        mask_a = ap.active & (ap.priority == p)
        prob = qp_step(ap, x, mask_a, finalized, eps, pin_free=pin_free)
        if project:
            x, _, _ = _tree_project(
                prob.target, prob.w, prob.lo, prob.hi, ap.tree, n_depths
            )
            x = repair(x, ap, n_depths)
            finalized = finalized | mask_a
            solves += 1
            continue
        state = solver.SolverState(x, state.t, state.y_tree, state.y_sla, state.y_imp)
        state, stats = solver.solve(prob, ap.tree, ap.sla, state, opts)
        x = repair(state.x, ap, n_depths)
        finalized = finalized | mask_a
        solves += 1
        iters += int(stats.iterations)
        conv &= bool(stats.converged)
        cert &= bool(stats.certified)
        maxres = max(maxres, float(stats.primal_res))
    return x, state, PhaseStats(solves, iters, conv, maxres, cert)


def run_maxmin_phase(
    ap: AllocProblem,
    x: jnp.ndarray,
    opt_set: jnp.ndarray,
    free_set: jnp.ndarray,
    opts: solver.SolverOptions,
    eps: float = 1e-5,
    warm: solver.SolverState | None = None,
    max_rounds: int = MAX_ROUNDS,
    use_waterfill: bool = True,
) -> tuple[jnp.ndarray, solver.SolverState, PhaseStats]:
    """Algorithm 2: iterated max-min LP with saturation detection.

    Phase II: ``opt_set`` = active, ``free_set`` = idle.
    Phase III: ``opt_set`` = idle, ``free_set`` = empty (active pinned).

    When no tenant SLAs are present the feasible set is box + tree only and
    the iterated-LP limit is the lexicographic max-min allocation, which the
    exact water-filling sweep computes directly (``use_waterfill=True``,
    cross-validated against the LP path in tests).  With SLAs the LP path is
    required — tenant rows couple devices across subtrees.
    """
    n, m, k = ap.n, ap.tree.m, ap.sla.k
    if use_waterfill and k == 0:
        from repro.core.waterfill import waterfill_arrays

        x_wf = waterfill_arrays(
            np.asarray(ap.tree.start),
            np.asarray(ap.tree.end),
            np.asarray(ap.tree.cap),
            np.asarray(ap.u),
            np.asarray(x),
            np.asarray(opt_set),
        )
        state = warm if warm is not None else solver.SolverState.zeros(
            n, m, k, ap.l.dtype
        )
        return jnp.asarray(x_wf), state, PhaseStats(0, 0, True, 0.0)
    dtype = ap.l.dtype
    state = warm if warm is not None else solver.SolverState.zeros(n, m, k, dtype)
    # Devices with no slack at entry (e.g. already at u after Phase I, or under
    # a cap Phase I left tight) must be frozen before the first round —
    # otherwise they force t* = 0 and the eps-term would distribute surplus
    # arbitrarily instead of max-min fairly.
    mask_a = opt_set & ~saturated_mask(x, ap, opt_set)
    n_depths = ap.n_tree_depths()
    solves = iters = 0
    conv = cert = True
    maxres = 0.0
    for _ in range(max_rounds):
        if not bool(np.asarray(mask_a).any()):
            break
        mask_f = ~(mask_a | free_set)
        prob = lp_step(ap, x, mask_a, mask_f, free_set, eps)
        state = solver.SolverState(
            x, jnp.zeros((), dtype), state.y_tree, state.y_sla, state.y_imp
        )
        state, stats = solver.solve(prob, ap.tree, ap.sla, state, opts)
        # The exact max-min iteration never moves a non-free device below
        # its round-entry value (improvement rows force x >= base + t,
        # t >= 0), but those rows are dualized: a truncated solve can leave
        # the primal below base, silently destroying tenant minimums that
        # Phase I enforced.  Clamp to the invariant before the repair.
        x_cand = jnp.where(free_set, state.x, jnp.maximum(state.x, x))
        x_new = repair(x_cand, ap, n_depths)
        solves += 1
        iters += int(stats.iterations)
        conv &= bool(stats.converged)
        cert &= bool(stats.certified)
        maxres = max(maxres, float(stats.primal_res))
        sat = saturated_mask(x_new, ap, mask_a)
        t_star = float(state.t)
        no_new_sat = not bool(np.asarray(sat).any())
        x = x_new
        if t_star <= SAT_TOL and no_new_sat:
            break  # no measurable head-room left and nothing to freeze
        mask_a = mask_a & ~sat
    return x, state, PhaseStats(solves, iters, conv, maxres, cert)
