"""The three-phase allocation driver (Algorithm 3), fully jitted and
batched under ``jax.vmap``.

:func:`solve_three_phase` is the one three-phase driver.  It runs one
scenario as a fixed-shape jax program:

* the Phase I priority sweep is a ``lax.scan`` over the problem's
  precomputed priority-level metadata (``AllocProblem.priority_levels``),
  with per-scenario empty levels skipped by ``lax.cond``;
* the Phase II/III saturation rounds are a ``lax.while_loop`` over a
  :class:`BatchedStepState`, with the two exit tests (empty optimized set;
  no measurable head-room and nothing newly saturated) evaluated as traced
  predicates;
* the step problems (``qp_step``, ``lp_step``), saturation detection and
  the exact feasibility repair (a fixed-trip ``fori_loop``) are the shared
  builders of :mod:`repro.core.phases`;
* on SLA-free problems each Phase I level QP is the exact weighted tree
  projection :func:`repro.core.waterfill.tree_project_jax`, and the
  max-min fast path is the level-wise tree projection
  :func:`repro.core.waterfill.waterfill_project_jax`.

The whole three-phase policy compiles once per
``(n, m, k, n_priority_levels)`` shape and is ``vmap``-ed over K request
scenarios into one accelerator program.  :func:`optimize_batched` is the
K-lane entry point, :func:`repro.core.nvpax.optimize` the one-lane case of
the same program, and :class:`repro.core.engine.AllocEngine` and the
sharded fleet trace :func:`solve_three_phase` into their own step programs.
"""

from __future__ import annotations

import contextlib
import functools
import time
from dataclasses import dataclass
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import phases, solver
from repro.core.nvpax import NvpaxOptions
from repro.core.problem import AllocProblem
from repro.core.solver.options import KKT_HIST_BUCKETS
from repro.core.waterfill import tree_project_jax, waterfill_project_jax
from repro.obs import recorder as obs_recorder
from repro.obs.stats import StepStats

__all__ = [
    "BatchMeta",
    "BatchedStepState",
    "BatchedAllocResult",
    "stack_problems",
    "solve_three_phase",
    "optimize_batched",
    "PhaseCostModel",
    "calibrate_phase_cost",
]


class BatchMeta(NamedTuple):
    """Static (hashable) metadata parameterizing one engine compilation.

    Derived from the problem by :func:`batch_meta`; the engine jits once per
    distinct value (plus the ``(n, m, k)`` array shapes).
    """

    levels: tuple[int, ...]  # descending distinct priority values
    n_depths: int  # PDN tree depth count (repair fori-loop trips)
    pin_free: bool  # Phase I free-device pinning (paper 4.3.1)
    max_rounds: int  # Phase II/III saturation-round bound
    use_waterfill: bool  # SLA-free max-min fast path
    run_phase2: bool
    run_phase3: bool
    eps: float  # regularization weight
    # incremental certify-first stepping tolerances (watts; PR 7 — see
    # repro.core.solver.certify); only consulted when a carry is passed
    certify_tol: float = 1e-9
    certify_margin: float = 1e-2


class BatchedStepState(NamedTuple):
    """Carry of the masked scan/while programs (one scenario's solve)."""

    x: jnp.ndarray  # [n] current allocation
    solver: solver.SolverState  # warm-started inner-solver state
    mask: jnp.ndarray  # [n] bool: finalized set (P1) / optimized set (P2, P3)
    solves: jnp.ndarray  # int32: inner solves actually executed
    iterations: jnp.ndarray  # int32: cumulative PDHG iterations
    converged: jnp.ndarray  # bool: all executed solves converged
    certified: jnp.ndarray  # bool: all executed solves KKT-certified
    done: jnp.ndarray  # bool: early-exit flag (max-min rounds)
    # flight-recorder gauges (PR 8): worst KKT residual over executed
    # solves, cumulative restarts, and the in-loop KKT-score histogram
    kkt_res: jnp.ndarray  # dtype scalar
    restarts: jnp.ndarray  # int32
    kkt_hist: jnp.ndarray  # [KKT_HIST_BUCKETS] int32
    # search steps of the tree projection (Phase I) or the max-min fill
    # (Phases II/III) on problems with no tenant rows, and the tree levels
    # whose search ran
    search_steps: jnp.ndarray  # int32
    search_levels: jnp.ndarray  # int32


@dataclass
class BatchedAllocResult:
    """K scenarios' worth of :class:`repro.core.nvpax.AllocResult`."""

    allocation: np.ndarray  # [K, n] final feasible allocations
    phase1: np.ndarray  # [K, n]
    phase2: np.ndarray  # [K, n]
    warm_state: Any  # batched phases.WarmCarry ([K, ...] leaves)
    wall_time_s: float
    stats: dict[str, Any]  # per-scenario arrays: solves/iterations/converged
    # incremental-mode anchor for the next step ([K, ...] leaves; None unless
    # a carry was threaded in — see repro.core.solver.certify)
    carry: Any = None
    # updated per-lane flight-recorder state (None unless one was passed in
    # — see repro.obs.recorder)
    recorder: Any = None


def batch_meta(ap: AllocProblem, options: NvpaxOptions) -> BatchMeta:
    """Static engine metadata from a (possibly stacked) problem."""
    return BatchMeta(
        levels=ap.priority_levels(active_only=True),
        n_depths=ap.n_tree_depths(),
        pin_free=ap.pin_free_ok(),
        max_rounds=options.max_rounds,
        use_waterfill=options.use_waterfill,
        run_phase2=options.run_phase2,
        run_phase3=options.run_phase3,
        eps=options.eps,
        certify_tol=options.certify_tol,
        certify_margin=options.certify_margin,
    )


def stack_problems(aps: Sequence[AllocProblem]) -> AllocProblem:
    """Stack K control-step problems into one with ``[K, n]`` fleet leaves.

    All scenarios must share the PDN and SLA topology (same datacenter,
    different telemetry/activity/priorities) — that is what makes the
    batched solve one fixed-shape program.  Raises ``ValueError`` on
    topology mismatch.
    """
    if not aps:
        raise ValueError("need at least one AllocProblem")
    ref = aps[0]
    for i, ap in enumerate(aps[1:], start=1):
        for name, a, b in [
            ("tree.start", ref.tree.start, ap.tree.start),
            ("tree.end", ref.tree.end, ap.tree.end),
            ("tree.cap", ref.tree.cap, ap.tree.cap),
            ("tree.depth", ref.tree.depth, ap.tree.depth),
            ("sla.dev", ref.sla.dev, ap.sla.dev),
            ("sla.ten", ref.sla.ten, ap.sla.ten),
            ("sla.lo", ref.sla.lo, ap.sla.lo),
            ("sla.hi", ref.sla.hi, ap.sla.hi),
        ]:
            if a is b:  # shared topology object (controller path): no D2H compare
                continue
            if a.shape != b.shape or not bool(
                np.array_equal(np.asarray(a), np.asarray(b))
            ):
                raise ValueError(f"scenario {i} differs from scenario 0 in {name}")

    def stk(leaf):
        return jnp.stack([getattr(ap, leaf) for ap in aps])

    return ref._replace(
        l=stk("l"),
        u=stk("u"),
        r=stk("r"),
        priority=stk("priority"),
        active=stk("active"),
        weight_scale=stk("weight_scale"),
    )


# ---------------------------------------------------------------------------
# single-scenario trace-safe engine
# ---------------------------------------------------------------------------


def _phase1_scan(
    ap: AllocProblem,
    meta: BatchMeta,
    opts: solver.SolverOptions,
    warm: solver.SolverState,
    skip: jnp.ndarray | None = None,
) -> BatchedStepState:
    """Algorithm 1 as a ``lax.scan`` over the static priority levels.

    ``skip`` (incremental mode) gates every level's solve off: the scan
    returns its init state untouched, and the caller substitutes the
    carried Phase I point.  Traced, so skip/solve transitions share one
    compilation.
    """
    n = ap.n
    init = BatchedStepState(
        x=ap.l,
        solver=warm,
        mask=jnp.zeros((n,), bool),
        solves=jnp.zeros((), jnp.int32),
        iterations=jnp.zeros((), jnp.int32),
        converged=jnp.asarray(True),
        certified=jnp.asarray(True),
        done=jnp.asarray(False),
        kkt_res=jnp.zeros((), ap.l.dtype),
        restarts=jnp.zeros((), jnp.int32),
        kkt_hist=jnp.zeros((KKT_HIST_BUCKETS,), jnp.int32),
        search_steps=jnp.zeros((), jnp.int32),
        search_levels=jnp.zeros((), jnp.int32),
    )
    if not meta.levels:
        return init

    project = meta.use_waterfill and ap.sla.k == 0

    def level_step(st: BatchedStepState, p):
        mask_a = ap.active & (ap.priority == p)

        def run(st: BatchedStepState) -> BatchedStepState:
            prob = phases.qp_step(
                ap, st.x, mask_a, st.mask, meta.eps, pin_free=meta.pin_free
            )
            if project:
                # no tenant rows: the level QP is a tree projection, solved
                # exactly; the solver state passes through untouched
                x, steps, levels = tree_project_jax(
                    prob.target, prob.w, prob.lo, prob.hi, ap.tree, meta.n_depths
                )
                return st._replace(
                    x=phases.repair(x, ap, meta.n_depths),
                    mask=st.mask | mask_a,
                    solves=st.solves + 1,
                    search_steps=st.search_steps + steps,
                    search_levels=st.search_levels + levels,
                )
            sol = solver.SolverState(
                st.x, st.solver.t, st.solver.y_tree, st.solver.y_sla, st.solver.y_imp
            )
            sol, stats = solver.solve(prob, ap.tree, ap.sla, sol, opts)
            x = phases.repair(sol.x, ap, meta.n_depths)
            res = jnp.maximum(
                jnp.maximum(stats.primal_res, stats.dual_res), stats.comp_res
            )
            return BatchedStepState(
                x=x,
                solver=sol,
                mask=st.mask | mask_a,
                solves=st.solves + 1,
                iterations=st.iterations + stats.iterations.astype(jnp.int32),
                converged=st.converged & stats.converged,
                certified=st.certified & stats.certified,
                done=st.done,
                kkt_res=jnp.maximum(st.kkt_res, res),
                restarts=st.restarts + stats.restarts,
                kkt_hist=st.kkt_hist + stats.score_hist,
                search_steps=st.search_steps,
                search_levels=st.search_levels,
            )

        # sweep only the levels present among this scenario's active
        # devices: an empty level has nothing to solve
        pred = jnp.any(mask_a)
        if skip is not None:
            pred = pred & ~skip
        st = lax.cond(pred, run, lambda s: s, st)
        return st, None

    levels = jnp.asarray(meta.levels, ap.priority.dtype)
    final, _ = lax.scan(level_step, init, levels)
    return final


def _maxmin_loop(
    ap: AllocProblem,
    x: jnp.ndarray,
    opt_set: jnp.ndarray,
    free_set: jnp.ndarray,
    meta: BatchMeta,
    opts: solver.SolverOptions,
    warm: solver.SolverState,
    iters_before: jnp.ndarray | None = None,
    budget: jnp.ndarray | None = None,
    skip: jnp.ndarray | None = None,
) -> BatchedStepState:
    """Algorithm 2 as a ``lax.while_loop`` (Phase II/III shared driver).

    ``budget`` (with ``iters_before``, the cumulative PDHG iterations spent
    by earlier phases) is the anytime/deadline mode: the saturation loop
    stops as soon as the cumulative iteration count crosses the budget.
    Every round ends with the exact feasibility repair, so the truncated
    allocation is feasible: the driver is anytime at phase and round
    boundaries.

    ``skip`` (incremental mode) enters the loop condition, so a certified
    step exits before the first round — and under ``vmap`` a skipped lane
    is frozen by the while-loop batching rule while dirty lanes keep
    iterating (the "masked solve").  The caller substitutes the carried
    allocation for skipped lanes.
    """
    dtype = ap.l.dtype
    if meta.use_waterfill and ap.sla.k == 0:
        x_wf, steps, levels = waterfill_project_jax(
            x, opt_set, ap.tree, ap.u, meta.n_depths
        )
        return BatchedStepState(
            x=x_wf,
            solver=warm,
            mask=jnp.zeros_like(opt_set),
            solves=jnp.zeros((), jnp.int32),
            iterations=jnp.zeros((), jnp.int32),
            converged=jnp.asarray(True),
            certified=jnp.asarray(True),
            done=jnp.asarray(True),
            kkt_res=jnp.zeros((), dtype),
            restarts=jnp.zeros((), jnp.int32),
            kkt_hist=jnp.zeros((KKT_HIST_BUCKETS,), jnp.int32),
            search_steps=steps,
            search_levels=levels,
        )

    # freeze devices with no slack at entry: otherwise they force t* = 0 and
    # the eps-term would spread the surplus instead of raising it max-min
    mask0 = opt_set & ~phases.saturated_mask(x, ap, opt_set)
    init = BatchedStepState(
        x=x,
        solver=warm,
        mask=mask0,
        solves=jnp.zeros((), jnp.int32),
        iterations=jnp.zeros((), jnp.int32),
        converged=jnp.asarray(True),
        certified=jnp.asarray(True),
        done=jnp.asarray(False),
        kkt_res=jnp.zeros((), dtype),
        restarts=jnp.zeros((), jnp.int32),
        kkt_hist=jnp.zeros((KKT_HIST_BUCKETS,), jnp.int32),
        search_steps=jnp.zeros((), jnp.int32),
        search_levels=jnp.zeros((), jnp.int32),
    )

    def cond(st: BatchedStepState):
        live = (~st.done) & (st.solves < meta.max_rounds) & jnp.any(st.mask)
        if budget is not None:
            live = live & (iters_before + st.iterations < budget)
        if skip is not None:
            live = live & ~skip
        return live

    def body(st: BatchedStepState) -> BatchedStepState:
        mask_f = ~(st.mask | free_set)
        prob = phases.lp_step(ap, st.x, st.mask, mask_f, free_set, meta.eps)
        sol = solver.SolverState(
            st.x,
            jnp.zeros((), dtype),
            st.solver.y_tree,
            st.solver.y_sla,
            st.solver.y_imp,
        )
        sol, stats = solver.solve(prob, ap.tree, ap.sla, sol, opts)
        # monotone non-decrease on non-free devices: the dualized
        # improvement rows guarantee it only at convergence, so enforce it
        # against truncated solves (keeps Phase I's tenant minimums intact
        # through stalled LP rounds)
        x_cand = jnp.where(free_set, sol.x, jnp.maximum(sol.x, st.x))
        x_new = phases.repair(x_cand, ap, meta.n_depths)
        sat = phases.saturated_mask(x_new, ap, st.mask)
        # stop when no measurable head-room is left AND nothing newly
        # saturated needs freezing
        done = (sol.t <= phases.SAT_TOL) & ~jnp.any(sat)
        res = jnp.maximum(
            jnp.maximum(stats.primal_res, stats.dual_res), stats.comp_res
        )
        return BatchedStepState(
            x=x_new,
            solver=sol,
            mask=st.mask & ~sat,
            solves=st.solves + 1,
            iterations=st.iterations + stats.iterations.astype(jnp.int32),
            converged=st.converged & stats.converged,
            certified=st.certified & stats.certified,
            done=done,
            kkt_res=jnp.maximum(st.kkt_res, res),
            restarts=st.restarts + stats.restarts,
            kkt_hist=st.kkt_hist + stats.score_hist,
            search_steps=st.search_steps,
            search_levels=st.search_levels,
        )

    return lax.while_loop(cond, body, init)


def solve_three_phase(
    ap: AllocProblem,
    meta: BatchMeta,
    opts: solver.SolverOptions,
    warm: phases.WarmCarry | None = None,
    iter_budget: jnp.ndarray | int | None = None,
    carry: solver.IncrementalCarry | None = None,
):
    """One scenario's full Algorithm 3, trace-safe (jit/vmap-able).

    ``warm`` is the per-phase carry from the previous control step (see
    :class:`repro.core.phases.WarmCarry`): each phase warm-starts its duals
    from the same phase's previous end state, with the primal chained
    through the current step.

    ``iter_budget`` is the deadline/anytime mode, ``NvpaxOptions.deadline_s``
    in iteration space (callers derive the budget from a wall-clock
    deadline and a calibrated per-iteration cost, see
    :func:`calibrate_phase_cost`): Phase I always runs — it carries
    feasibility and request satisfaction — and each
    refinement phase (II: active surplus, III: idle surplus) starts only if
    the cumulative PDHG iteration count is still under budget, then stops at
    the first saturation round that crosses it.  Passing a traced/concrete
    int32 scalar changes the budget without recompilation.

    ``carry`` (incremental mode, PR 7) is the previous accepted step's
    :class:`repro.core.solver.certify.IncrementalCarry`: a fused certify
    pass runs first, and on success the carried point short-circuits the
    whole program (full skip) or Phase I only (Phase I skip) — as traced
    predicates gating the existing loops, so skip/solve transitions never
    recompile.

    Returns ``(x1, x2, x3, warm_carry, stats_dict)`` with jnp leaves;
    ``stats["truncated"]`` is True when refinement work was skipped or cut
    short by the budget; ``stats["skipped"]``/``stats["certify_pass"]`` are
    traced bools present on every path.
    """
    n, m, k = ap.n, ap.tree.m, ap.sla.k
    dtype = ap.l.dtype
    w1 = warm.p1 if warm is not None else solver.SolverState.zeros(n, m, k, dtype)
    budget = None if iter_budget is None else jnp.asarray(iter_budget, jnp.int32)

    if carry is not None:
        dec = solver.certify_step(
            ap,
            carry,
            meta.n_depths,
            tol=meta.certify_tol,
            margin=meta.certify_margin,
            opts=opts,
        )
        skip, skip_p1 = dec.skip, dec.skip_p1
        skip_any = skip | skip_p1
    else:
        skip = skip_any = None

    with jax.named_scope("phase1"):
        p1 = _phase1_scan(ap, meta, opts, w1, skip=skip_any)
    if carry is not None:
        # substitute the carried Phase I point (both tiers reuse it)
        carried_sol = solver.SolverState(carry.x1, w1.t, w1.y_tree, w1.y_sla, w1.y_imp)
        p1 = p1._replace(
            x=jnp.where(skip_any, carry.x1, p1.x),
            solver=jax.tree_util.tree_map(
                lambda c, s: jnp.where(skip_any, c, s), carried_sol, p1.solver
            ),
        )
    x1 = p1.x
    truncated = jnp.asarray(False)

    def skipped(x, sol) -> BatchedStepState:
        return BatchedStepState(
            x=x,
            solver=sol,
            mask=jnp.zeros_like(ap.active),
            solves=jnp.zeros((), jnp.int32),
            iterations=jnp.zeros((), jnp.int32),
            converged=jnp.asarray(True),
            certified=jnp.asarray(True),
            done=jnp.asarray(False),
            kkt_res=jnp.zeros((), dtype),
            restarts=jnp.zeros((), jnp.int32),
            kkt_hist=jnp.zeros((KKT_HIST_BUCKETS,), jnp.int32),
            search_steps=jnp.zeros((), jnp.int32),
            search_levels=jnp.zeros((), jnp.int32),
        )

    def refine(x, sol, opt_set, free_set, iters_before):
        """One budget-gated max-min phase; returns (state, truncated_flag)."""
        if budget is None:
            st = _maxmin_loop(ap, x, opt_set, free_set, meta, opts, sol, skip=skip)
            return st, jnp.asarray(False)
        start_ok = iters_before < budget

        def run(args):
            return _maxmin_loop(
                ap, args[0], opt_set, free_set, meta, opts, args[1],
                iters_before, budget, skip=skip,
            )

        st = lax.cond(start_ok, run, lambda args: skipped(*args), (x, sol))
        # cut short: phase never started, or the loop exited on the budget
        # test with unsaturated optimizable devices still holding head-room
        work_left = (~st.done) & jnp.any(st.mask) & (st.solves < meta.max_rounds)
        cut = (~start_ok) | (work_left & (iters_before + st.iterations >= budget))
        if skip is not None:
            # a certified skip is not a truncation
            cut = cut & ~skip
        return st, cut

    w2 = phases.merge_warm(p1.solver, warm.p2 if warm is not None else None)
    if meta.run_phase2:
        with jax.named_scope("phase2"):
            p2, cut2 = refine(x1, w2, ap.active, ap.idle, p1.iterations)
        if carry is not None:
            p2 = p2._replace(x=jnp.where(skip, carry.x2, p2.x))
        x2 = p2.x
        truncated = truncated | cut2
    else:
        p2 = p1._replace(solver=w2,
                         solves=jnp.zeros((), jnp.int32),
                         iterations=jnp.zeros((), jnp.int32),
                         converged=jnp.asarray(True),
                         certified=jnp.asarray(True),
                         kkt_res=jnp.zeros((), dtype),
                         restarts=jnp.zeros((), jnp.int32),
                         kkt_hist=jnp.zeros((KKT_HIST_BUCKETS,), jnp.int32),
                         search_steps=jnp.zeros((), jnp.int32),
                         search_levels=jnp.zeros((), jnp.int32))
        x2 = x1

    w3 = phases.merge_warm(p2.solver, warm.p3 if warm is not None else None)
    if meta.run_phase3:
        empty = jnp.zeros_like(ap.active)
        with jax.named_scope("phase3"):
            p3, cut3 = refine(x2, w3, ap.idle, empty,
                              p1.iterations + p2.iterations)
        if carry is not None:
            p3 = p3._replace(x=jnp.where(skip, dec.x_snap, p3.x))
        x3 = p3.x
        truncated = truncated | cut3
    else:
        p3 = p2._replace(solver=w3,
                         solves=jnp.zeros((), jnp.int32),
                         iterations=jnp.zeros((), jnp.int32),
                         converged=jnp.asarray(True),
                         certified=jnp.asarray(True),
                         kkt_res=jnp.zeros((), dtype),
                         restarts=jnp.zeros((), jnp.int32),
                         kkt_hist=jnp.zeros((KKT_HIST_BUCKETS,), jnp.int32),
                         search_steps=jnp.zeros((), jnp.int32),
                         search_levels=jnp.zeros((), jnp.int32))
        x3 = x2

    stats = {
        "solves": p1.solves + p2.solves + p3.solves,
        "iterations": p1.iterations + p2.iterations + p3.iterations,
        # per-phase PDHG iteration split: groundwork for a per-phase deadline
        # cost model (the uniform per-iteration estimate errs when phase
        # mixes shift; see ROADMAP deadline-calibration item)
        "iterations_p1": p1.iterations,
        "iterations_p2": p2.iterations,
        "iterations_p3": p3.iterations,
        # search steps of the max-min fill and the tree levels whose search
        # ran (the SLA-free Phase II/III path, which runs no PDHG iteration)
        # search steps of Phase I's tree projection and the tree levels
        # whose search ran (the SLA-free path, which runs no PDHG iteration)
        "project_steps_p1": p1.search_steps,
        "project_levels_p1": p1.search_levels,
        "waterfill_rounds_p2": p2.search_steps,
        "waterfill_rounds_p3": p3.search_steps,
        "waterfill_levels_p2": p2.search_levels,
        "waterfill_levels_p3": p3.search_levels,
        "converged": p1.converged & p2.converged & p3.converged,
        "kkt_certified": p1.certified & p2.certified & p3.certified,
        "truncated": truncated,
        # flight-recorder gauges: worst residual over phases, restart and
        # in-loop KKT-score-histogram totals
        "kkt_res": jnp.maximum(jnp.maximum(p1.kkt_res, p2.kkt_res), p3.kkt_res),
        "restarts": p1.restarts + p2.restarts + p3.restarts,
        "kkt_hist": p1.kkt_hist + p2.kkt_hist + p3.kkt_hist,
        # incremental certify outcome, on every path (False consts when no
        # carry was given) — jnp scalars so they survive vmap
        "skipped": jnp.asarray(False) if carry is None else skip,
        "certify_pass": jnp.asarray(False) if carry is None else skip_any,
    }
    wcarry = phases.WarmCarry(p1.solver, p2.solver, p3.solver)
    return x1, x2, x3, wcarry, stats


def _record_batch(
    cfg: obs_recorder.RecorderConfig,
    rec: obs_recorder.RecorderState,
    stats: dict,
    alloc: jnp.ndarray,
    stacked: AllocProblem,
) -> obs_recorder.RecorderState:
    """Append one flight-record row per scenario lane (vmapped; pure
    fixed-shape ops, so recording shares the unrecorded compilation)."""
    sla = stacked.sla
    nrows = int(sla.lo.shape[0])

    def one(rec_one, st_one, a, l, u, r, active):
        r_eff = jnp.where(active, jnp.clip(r, l, u), 0.0)
        margin = obs_recorder.sla_min_margin(a, sla.dev, sla.ten, sla.lo, nrows)
        m = obs_recorder.step_metrics(st_one, a, r_eff, margin)
        return obs_recorder.record_step(cfg, rec_one, m, a)

    return jax.vmap(one)(
        rec, stats, alloc, stacked.l, stacked.u, stacked.r, stacked.active
    )


@functools.partial(jax.jit, static_argnames=("meta", "opts", "rec_cfg"))
def _solve_batched(
    stacked: AllocProblem,
    meta: BatchMeta,
    opts: solver.SolverOptions,
    warm: phases.WarmCarry | None,
    iter_budget: jnp.ndarray | None = None,
    carry: solver.IncrementalCarry | None = None,
    rec: obs_recorder.RecorderState | None = None,
    rec_cfg: obs_recorder.RecorderConfig | None = None,
):
    """vmap of the three-phase engine over the leading scenario axis.

    ``carry`` is an :class:`repro.core.solver.certify.IncrementalCarry` with
    ``[K, ...]`` leaves (incremental mode).  Per-scenario certify flags gate
    the inner loops (dirty lanes iterate, clean lanes are frozen by the
    while-loop batching rule), and when *every* scenario certifies a full
    skip a scalar ``lax.cond`` short-circuits the whole vmapped solve to the
    O(matvec) assembly below — that is what collapses the quasi-static fleet
    step to certify cost.

    ``rec``/``rec_cfg`` (flight recorder, PR 8) thread per-lane
    :class:`repro.obs.recorder.RecorderState` pytrees through the step:
    recording happens AFTER the all-skip short-circuit so both the fast and
    vmapped paths log their step.  Returns ``(x1, x2, x3, warm_carry, stats,
    new_carry, rec)``.
    """
    tree, sla = stacked.tree, stacked.sla
    fleet_axes = (0, 0, 0, 0, 0, 0)
    fleet_leaves = (
        stacked.l,
        stacked.u,
        stacked.r,
        stacked.priority,
        stacked.active,
        stacked.weight_scale,
    )

    def one(l, u, r, priority, active, weight_scale, warm_one, carry_one):
        ap = AllocProblem(
            l=l, u=u, r=r, priority=priority, active=active,
            tree=tree, sla=sla, weight_scale=weight_scale,
        )
        x1, x2, x3, wc, stats = solve_three_phase(
            ap, meta, opts, warm_one, iter_budget, carry_one
        )
        new_carry = solver.update_carry(
            carry_one,
            ap,
            x1,
            x2,
            x3,
            stats["skipped"],
            stats["certify_pass"] & ~stats["skipped"],
        )
        return x1, x2, x3, wc, stats, new_carry

    # warm/carry are pytrees with [K, ...] leaves (or None)
    warm_axes = None if warm is None else 0

    def run_vmapped(c):
        axes = fleet_axes + (warm_axes, None if c is None else 0)
        return jax.vmap(one, in_axes=axes)(*fleet_leaves, warm, c)

    def finish(out):
        x1, x2, x3, wc, stats, new_carry = out
        new_rec = rec
        if rec is not None and rec_cfg is not None:
            new_rec = _record_batch(rec_cfg, rec, stats, x3, stacked)
        return x1, x2, x3, wc, stats, new_carry, new_rec

    if carry is None or warm is None:
        # no anchor yet (or no warm state to thread through the all-skip
        # assembly): per-lane gating alone
        return finish(run_vmapped(carry))

    def cert_one(l, u, r, priority, active, weight_scale, carry_one):
        ap = AllocProblem(
            l=l, u=u, r=r, priority=priority, active=active,
            tree=tree, sla=sla, weight_scale=weight_scale,
        )
        return solver.certify_step(
            ap,
            carry_one,
            meta.n_depths,
            tol=meta.certify_tol,
            margin=meta.certify_margin,
            opts=opts,
        )

    dec = jax.vmap(cert_one, in_axes=fleet_axes + (0,))(*fleet_leaves, carry)
    kk = stacked.l.shape[0]

    def fast(_):
        # every scenario certified: assemble the exact all-skip outputs the
        # vmapped program would produce, without running it
        p1_sol = warm.p1._replace(x=carry.x1)
        w2 = phases.merge_warm(p1_sol, warm.p2)
        w3 = phases.merge_warm(w2, warm.p3)
        zi = jnp.zeros((kk,), jnp.int32)
        yes = jnp.ones((kk,), bool)
        stats = {
            "solves": zi,
            "iterations": zi,
            "iterations_p1": zi,
            "iterations_p2": zi,
            "iterations_p3": zi,
            "project_steps_p1": zi,
            "project_levels_p1": zi,
            "waterfill_rounds_p2": zi,
            "waterfill_rounds_p3": zi,
            "waterfill_levels_p2": zi,
            "waterfill_levels_p3": zi,
            "converged": yes,
            "kkt_certified": yes,
            "truncated": jnp.zeros((kk,), bool),
            "skipped": dec.skip,
            "certify_pass": dec.skip | dec.skip_p1,
            "kkt_res": jnp.zeros((kk,), stacked.l.dtype),
            "restarts": zi,
            "kkt_hist": jnp.zeros((kk, KKT_HIST_BUCKETS), jnp.int32),
        }
        wcarry = phases.WarmCarry(p1_sol, w2, w3)
        return carry.x1, carry.x2, dec.x_snap, wcarry, stats, carry

    def slow(_):
        return run_vmapped(carry)

    return finish(lax.cond(jnp.all(dec.skip), fast, slow, None))


# ---------------------------------------------------------------------------
# deadline calibration
# ---------------------------------------------------------------------------


class PhaseCostModel(NamedTuple):
    """Per-phase seconds-per-PDHG-iteration estimates (ROADMAP item: the
    uniform cost model erred when phase mixes shifted between calibration
    and serving).

    ``p1_s`` prices a Phase I (priority-sweep QP) iteration, ``p23_s`` a
    Phase II/III (saturation-round max-min LP) iteration — the two program
    shapes differ in per-solve overhead (level scan vs saturation loop,
    repair cadence), which a single number cannot capture.  ``mix`` is the
    (phase-1 fraction, phase-2+3 fraction) of iterations observed at
    calibration; callers with fresher information (e.g. the engine's
    last-step ``stats["phase_iterations"]``) pass their own mix.
    """

    p1_s: float
    p23_s: float
    mix: tuple[float, float]

    def cost_per_iter(self, mix: tuple[float, float] | None = None) -> float:
        f1, f23 = self.mix if mix is None else mix
        tot = max(f1 + f23, 1e-9)
        return (f1 * self.p1_s + f23 * self.p23_s) / tot

    def budget(
        self, deadline_s: float, mix: tuple[float, float] | None = None
    ) -> int:
        """Wall-clock deadline -> cumulative PDHG iteration budget."""
        return max(int(float(deadline_s) / self.cost_per_iter(mix)), 0)

    @classmethod
    def fit(
        cls,
        wall_p1: float,
        phases_p1: Sequence[int],
        wall_full: float,
        phases_full: Sequence[int],
    ) -> "PhaseCostModel":
        """Fit the two-probe measurement shared by the batched and engine
        calibrators: a Phase-I-only probe prices the QP sweep directly; the
        Phase II/III price is the full probe's residual wall time at that
        QP price, floored at half of it so a noisy subtraction cannot
        produce a near-zero price (and an exploding budget)."""
        c1 = wall_p1 / max(phases_p1[0], 1)
        it23 = phases_full[1] + phases_full[2]
        if it23 > 0:
            c23 = max(max(wall_full - c1 * phases_full[0], 0.0) / it23, 0.5 * c1)
        else:
            c23 = c1
        tot = sum(phases_full)
        if tot == 0:
            # no PDHG iteration at all (tenant-free problems, where Phase I
            # projects and the max-min phases fill): price each budgeted
            # iteration at the whole Phase-I-only probe
            return cls(p1_s=c1, p23_s=c23, mix=(1.0, 0.0))
        return cls(p1_s=c1, p23_s=c23, mix=(phases_full[0] / tot, it23 / tot))


# per-(shape, meta, opts) phase cost models
_ITER_COST_CACHE: dict[Any, PhaseCostModel] = {}

# effectively-unbounded budget: the full-solve probe runs the same compiled
# (budgeted) program the deadline path serves, so its timing includes the
# budget plumbing
_PROBE_FULL_BUDGET = 2**31 - 1


def calibrate_phase_cost(
    stacked: AllocProblem,
    meta: BatchMeta,
    opts: solver.SolverOptions,
) -> PhaseCostModel:
    """Measured per-phase seconds per PDHG iteration of the batched program.

    Two probes, each run twice (the first call pays the compile):

    * budget 1 — Phase I only (both refinement phases skipped): prices the
      QP sweep directly;
    * unbounded budget — the full three-phase program: the Phase II/III
      price is the residual wall time after subtracting the Phase I
      iterations at the QP price.

    Estimates include per-solve overhead (scaling setup, KKT checks), which
    biases costs high and therefore derived budgets low: deadline truncation
    errs on the early side, like a wall-clock check would.  Cached per
    (shape, meta, opts).
    """
    key = (
        tuple(stacked.l.shape), jnp.dtype(stacked.l.dtype).name, meta, opts,
    )
    if key not in _ITER_COST_CACHE:
        def probe(budget):
            b = jnp.asarray(budget, jnp.int32)
            _solve_batched(stacked, meta, opts, None, b)[2].block_until_ready()
            t0 = time.perf_counter()
            _, _, x3, _, stats, _, _ = _solve_batched(stacked, meta, opts, None, b)
            x3.block_until_ready()
            wall = time.perf_counter() - t0
            per_phase = [
                int(np.max(np.asarray(stats[f"iterations_p{i}"])))
                for i in (1, 2, 3)
            ]
            return wall, per_phase

        wall1, phases1 = probe(1)
        wall_f, phases_f = probe(_PROBE_FULL_BUDGET)
        _ITER_COST_CACHE[key] = PhaseCostModel.fit(wall1, phases1, wall_f, phases_f)
    return _ITER_COST_CACHE[key]


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------


def optimize_batched(
    aps: Sequence[AllocProblem] | AllocProblem,
    options: NvpaxOptions = NvpaxOptions(),
    warm: phases.WarmCarry | None = None,
    *,
    meta: BatchMeta | None = None,
    iter_budget: int | None = None,
    carry: Any = None,
    rec: Any = None,
    rec_cfg: Any = None,
) -> BatchedAllocResult:
    """Run Algorithm 3 on K scenarios as ONE jitted+vmapped program.

    ``aps`` is either a sequence of per-scenario :class:`AllocProblem`\\ s
    sharing PDN/SLA topology, or an already-stacked problem with ``[K, n]``
    fleet leaves (see :func:`stack_problems`).  ``warm`` optionally carries
    a batched solver state from a previous batched call (``[K, ...]``
    leaves) — e.g. the previous control step's, which cuts solver iterations
    on slowly-drifting telemetry (asserted in ``tests/test_engine.py``).

    ``meta`` pins the engine compilation (e.g. a topology-pinned
    :class:`repro.core.engine.AllocEngine` passes its construction-time
    metadata so per-step active-set changes cannot retrigger compilation);
    by default it is derived from the stacked problem.

    Deadline mode: ``options.deadline_s`` is honored by translating the
    wall-clock deadline into a per-scenario PDHG iteration budget via
    :func:`calibrate_phase_cost` (one-time per shape) — Phase I always runs,
    refinement phases are skipped or cut at saturation-round granularity,
    and ``stats["truncated"]`` reports per-scenario truncation.
    ``iter_budget`` passes an explicit budget instead (overrides
    ``deadline_s``).

    Incremental mode: ``carry`` threads the previous step's
    ``BatchedAllocResult.carry`` back in; per-scenario certify flags land in
    ``stats["skipped"]``/``stats["certify_pass"]`` (they survive the vmap as
    ``[K]`` arrays), and an all-skip batch collapses to certify cost.

    Flight recorder: ``rec``/``rec_cfg`` thread per-lane
    :class:`repro.obs.recorder.RecorderState` pytrees (``[K, ...]`` leaves,
    see :func:`repro.obs.recorder.init_batch`); the updated state comes back
    as ``BatchedAllocResult.recorder``.

    Each lane matches the one-lane :func:`repro.core.nvpax.optimize` to
    solver tolerance (asserted in ``tests/test_batched.py``).
    """
    out, iter_budget, wall = _run_batched(
        aps, options, warm, meta=meta, iter_budget=iter_budget, carry=carry,
        rec=rec, rec_cfg=rec_cfg,
    )
    x1, x2, x3, sol_state, stats, new_carry, new_rec = out
    return BatchedAllocResult(
        allocation=np.asarray(x3),
        phase1=np.asarray(x1),
        phase2=np.asarray(x2),
        warm_state=sol_state,
        wall_time_s=wall,
        carry=new_carry if carry is not None or options.incremental else None,
        recorder=new_rec if rec is not None else None,
        stats=StepStats.from_jit(
            stats,
            iter_budget=iter_budget,
            n_scenarios=int(x3.shape[0]),
        ),
    )


def _run_batched(
    aps: Sequence[AllocProblem] | AllocProblem,
    options: NvpaxOptions,
    warm: phases.WarmCarry | None = None,
    *,
    meta: BatchMeta | None = None,
    iter_budget: int | None = None,
    carry: Any = None,
    rec: Any = None,
    rec_cfg: Any = None,
):
    """Stack ``aps``, price ``options.deadline_s`` and run
    :func:`_solve_batched` under the options' precision: the shared body of
    :func:`optimize_batched` and the one-lane
    :func:`repro.core.nvpax.optimize`.

    Returns ``(outputs, iter_budget, wall_s)``: the program's seven outputs
    with ``[K, ...]`` leaves, the iteration budget served (``None``
    without a deadline) and the wall time through the allocation's
    readiness.
    """
    ctx = jax.enable_x64(True) if options.x64 else contextlib.nullcontext()
    t0 = time.perf_counter()
    with ctx:  # stack + solve under one x64 context (no silent f32 downcast)
        stacked = aps if isinstance(aps, AllocProblem) else stack_problems(aps)
        if stacked.l.ndim != 2:
            raise ValueError(
                f"expected stacked [K, n] fleet leaves, got shape {stacked.l.shape}"
            )
        if meta is None:
            meta = batch_meta(stacked, options)
        if iter_budget is None and options.deadline_s is not None:
            model = calibrate_phase_cost(stacked, meta, options.solver)
            iter_budget = model.budget(options.deadline_s)
        budget = (
            None if iter_budget is None else jnp.asarray(iter_budget, jnp.int32)
        )
        out = _solve_batched(
            stacked, meta, options.solver, warm, budget, carry, rec, rec_cfg
        )
        out[2].block_until_ready()
    return out, iter_budget, time.perf_counter() - t0
