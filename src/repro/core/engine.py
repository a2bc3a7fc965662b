"""Persistent allocation engine: compile-once control loop with zero-rebuild
steps.

:class:`AllocEngine` is the production serving shape of the allocator.  The
per-step cost of the rebuild-every-step path (``AllocProblem.build`` +
``nvpax.optimize``) is dominated by host-side work we re-pay every control
interval: topology re-derivation, device upload and request shaping.  The
engine is constructed **once per fleet** — PDN tree + SLA topology +
priority layout — and then serves every control step with zero host-side
rebuild work:

* construction precomputes everything shape-static: the
  :class:`~repro.core.problem.FleetTopology` device arrays, the
  :class:`~repro.core.batched.BatchMeta` (priority levels from the *full*
  priority layout, tree-depth count, the pin-free simplification) that pins
  one compilation for the life of the engine — per-step active-set changes
  are handled by the engine's traced empty-level skip, never by recompiling;
* :meth:`step` is one jitted program (``solve_three_phase`` at K=1):
  telemetry pre-processing (clip to box, idle -> l), all three phases,
  feasibility repair — a single dispatch with no phase-boundary host hops;
* warm starts are carried across control steps automatically, in both the
  one-scenario (:meth:`step`) and batched (:meth:`step_batched`) paths — an
  optimization, not a correctness dependency (:meth:`reset_warm` restores
  cold start, e.g. after fleet geometry changes);
* deadlines run in iteration space: ``options.deadline_s`` (or a per-call
  override) is translated into a PDHG iteration budget via a one-time
  calibrated per-iteration cost, with phase-boundary anytime semantics
  (``stats["truncated"]``) as in :func:`repro.core.nvpax.optimize`.

The engine is *shape*-pinned, not *value*-pinned: the fleet topology enters
the compiled program as traced arrays, so any same-shape change — a supply
drop rescaling node caps (:meth:`rescale_supply`), a per-step budget grant
from the fleet coordinator (:meth:`set_root_cap`), device box changes on
churn (:meth:`repin`) — swaps arrays on the pinned executable without
recompiling (asserted via :func:`trace_count` in ``tests/test_fleet.py``).
Only shape/static-metadata changes (device count, priority level set) need a
new engine.  :class:`repro.power.PowerController` and
:class:`repro.fleet.FleetOrchestrator` manage that lifecycle.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import phases
from repro.core.batched import (
    _PROBE_FULL_BUDGET,
    BatchMeta,
    BatchedAllocResult,
    PhaseCostModel,
    optimize_batched,
    solve_three_phase,
)
from repro.core.nvpax import AllocResult, NvpaxOptions
from repro.core.problem import AllocProblem, FleetTopology
from repro.core.solver import certify
from repro.core.treeops import SlaTopo
from repro.obs import recorder as obs_recorder
from repro.obs import spans
from repro.obs.stats import StepStats
from repro.pdn.tree import FlatPDN, check_caps_fund_minimums

__all__ = ["AllocEngine", "trace_count"]

_UNSET = object()

# Incremented each time the engine step program is (re)traced, i.e. once per
# compiled variant.  Lifecycle tests assert re-pins (cap/box swaps) leave it
# unchanged while shape changes advance it.
_N_TRACES = 0


def trace_count() -> int:
    """Number of times the engine step program has been traced (compiled)
    in this process.  Monotone; compare deltas, not absolute values."""
    return _N_TRACES


def _shape_requests(r, active, l, u):
    """Paper section 5.2 request shaping (trace-safe): clip to the device
    box; idle devices request ``l``.  Mirrors ``AllocProblem.build``'s host
    numpy version — the single jnp implementation for both engine paths."""
    return jnp.where(active, jnp.clip(r, l, u), l)


def _engine_solve(
    fleet,
    r,
    priority,
    active,
    warm,
    iter_budget,
    carry=None,
    rec=None,
    *,
    meta,
    opts,
    rec_cfg=None,
):
    """The whole control step as one traced program: request pre-processing
    (paper section 5.2) + certify-first incremental gate + three-phase solve
    + exact feasibility repair (+ optional flight-recorder append)."""
    global _N_TRACES
    _N_TRACES += 1  # executes at trace time only (side effect outside jnp ops)
    r = _shape_requests(r, active, fleet.l, fleet.u)
    ap = AllocProblem(
        l=fleet.l,
        u=fleet.u,
        r=r,
        priority=priority,
        active=active,
        tree=fleet.tree,
        sla=fleet.sla,
        weight_scale=fleet.weight_scale,
    )
    x1, x2, x3, sol, stats = solve_three_phase(ap, meta, opts, warm, iter_budget, carry)
    new_carry = certify.update_carry(
        carry, ap, x1, x2, x3, stats["skipped"],
        stats["certify_pass"] & ~stats["skipped"],
    )
    if rec is not None and rec_cfg is not None:
        nrows = int(fleet.sla.lo.shape[0])
        margin = obs_recorder.sla_min_margin(
            x3, fleet.sla.dev, fleet.sla.ten, fleet.sla.lo, nrows
        )
        # idle devices request l by shaping; zero them out of the
        # satisfaction denominator (they have no demand to satisfy)
        m = obs_recorder.step_metrics(
            stats, x3, jnp.where(active, r, 0.0), margin
        )
        rec = obs_recorder.record_step(rec_cfg, rec, m, x3)
    return x1, x2, x3, sol, stats, new_carry, rec


# One compiled executable per (shapes, meta, opts): engines over the same
# fleet geometry share it.  Donating the warm state (argnum 4) to reuse its
# buffers in place on accelerators is tempting but unsafe as-is: the carried
# state escapes via AllocResult.warm_state (the next step would invalidate
# buffers the caller still holds), and with run_phase2/3 disabled the carry
# aliases the same buffer in two leaves, which XLA rejects for donation.
# Revisit with accelerator CI + a copy-on-return boundary.
_engine_step_jit = jax.jit(
    _engine_solve,
    static_argnames=("meta", "opts", "rec_cfg"),
    # the recorder ring IS donation-safe (unlike the warm state above): the
    # caller holds no reference to the previous RecorderState once the step
    # returns the advanced one, so the [capacity, 16] ring updates in place
    # instead of being copied every step
    donate_argnames=("rec",),
)


class AllocEngine:
    """Construct-once / step-many allocation runtime for one fleet.

    Parameters mirror ``AllocProblem.build``: the PDN, optional tenant SLA
    topology, a fixed priority layout, and ``NvpaxOptions``.  ``step`` then
    takes only telemetry (+ optional scheduler active mask) and returns the
    same :class:`~repro.core.nvpax.AllocResult` as ``nvpax.optimize`` —
    matching it to solver tolerance (see ``tests/test_engine.py``).
    """

    def __init__(
        self,
        pdn: FlatPDN,
        *,
        sla: SlaTopo | None = None,
        priority: np.ndarray | None = None,
        options: NvpaxOptions | None = None,
        idle_threshold: float = 150.0,
        normalized: bool = False,
        dtype=jnp.float64,
        pin_free: bool | None = None,
        recorder: obs_recorder.RecorderConfig | bool | None = None,
    ):
        self.pdn = pdn
        self.options = options or NvpaxOptions()
        self.idle_threshold = float(idle_threshold)
        self.dtype = dtype
        # flight recorder (PR 8): True -> default config; a RecorderConfig
        # pins the ring shape.  State is lazily initialized per path (the
        # step() recorder is single-lane; step_batched keeps one [K, ...]
        # state per batch size, like the warm caches).
        if recorder is True:
            recorder = obs_recorder.RecorderConfig()
        self._rec_cfg: obs_recorder.RecorderConfig | None = recorder or None
        self._rec_state: obs_recorder.RecorderState | None = None
        self._rec_batched: dict[int, obs_recorder.RecorderState] = {}
        self._x64 = bool(self.options.x64) and dtype == jnp.float64
        with self._ctx():
            self.fleet = FleetTopology.from_pdn(
                pdn, sla=sla, normalized=normalized, dtype=dtype
            )
            if priority is None:
                priority = np.ones((pdn.n,), np.int32)
            self.priority_np = np.asarray(priority, np.int32)
            if (self.priority_np < 1).any():
                raise ValueError("priorities must be >= 1")
            self.priority = jnp.asarray(self.priority_np)
        sla_t = self.fleet.sla
        if pin_free is None:
            # auto: safe iff no tenant minimum can force a pinned-free
            # device upward.  Callers that re-pin SLA lower bounds at
            # runtime (set_sla_bounds with lo > 0 later) must pass False —
            # pin_free is compiled-in metadata (paper 4.3.1).
            pin_free = sla_t.k == 0 or not bool((np.asarray(sla_t.lo) > 0).any())
        # levels from the full priority layout (not the per-step active set):
        # the Phase I scan skips empty levels with a traced cond, so the
        # compiled program is pinned while per-step semantics match
        # nvpax.optimize's active-only sweep exactly.
        self.meta = BatchMeta(
            levels=tuple(sorted({int(p) for p in self.priority_np}, reverse=True)),
            n_depths=int(pdn.node_depth.max()) + 1 if pdn.m else 0,
            pin_free=pin_free,
            max_rounds=self.options.max_rounds,
            use_waterfill=self.options.use_waterfill,
            run_phase2=self.options.run_phase2,
            run_phase3=self.options.run_phase3,
            eps=self.options.eps,
            certify_tol=self.options.certify_tol,
            certify_margin=self.options.certify_margin,
        )
        # construction-time caps: rescale_supply scales are absolute vs these
        self._node_cap0 = np.asarray(pdn.node_cap, np.float64).copy()
        # host mirrors of the current pinned caps and per-node subtree
        # minimum draws, so the per-step set_root_cap fast path needs no
        # device readback and no O(n) revalidation (repin keeps them fresh)
        self._node_cap_np = self._node_cap0.copy()
        self._subtree_lmin = pdn.subtree_min_power()
        self._warm: phases.WarmCarry | None = None
        self._batched_warm: dict[int, Any] = {}
        # incremental (certify-first) anchors, carried only when
        # options.incremental — see repro.core.solver.certify
        self._inc_carry: Any = None
        self._inc_batched_carry: dict[int, Any] = {}
        self._cost_model: PhaseCostModel | None = None
        self.history: list[dict[str, Any]] = []

    def _ctx(self):
        return jax.enable_x64(True) if self._x64 else contextlib.nullcontext()

    @property
    def n(self) -> int:
        return self.pdn.n

    def reset_warm(self) -> None:
        """Drop carried solver state (next step/step_batched cold-starts).
        The flight recorder is telemetry, not solver state — it survives."""
        self._warm = None
        self._batched_warm.clear()
        self._inc_carry = None
        self._inc_batched_carry.clear()

    # -- flight recorder (PR 8) --------------------------------------------

    @property
    def recorder_config(self) -> obs_recorder.RecorderConfig | None:
        return self._rec_cfg

    def flush_recorder(self, *, reset: bool = False) -> dict[str, Any] | None:
        """Materialize the flight record(s) to host numpy (the recorder's
        only host transfer).  Returns ``{"step": flush, "batched": {K:
        [per-lane flushes]}}`` with absent keys for paths never stepped;
        None when the engine was built without a recorder."""
        if self._rec_cfg is None:
            return None
        out: dict[str, Any] = {}
        if self._rec_state is not None:
            out["step"] = obs_recorder.flush(self._rec_state, self._rec_cfg)
        if self._rec_batched:
            out["batched"] = {
                K: obs_recorder.flush_lanes(st, self._rec_cfg)
                for K, st in self._rec_batched.items()
            }
        if reset:
            self._rec_state = None
            self._rec_batched.clear()
        return out

    # -- in-place topology re-pin (no recompile) ---------------------------

    def repin(
        self,
        *,
        dev_l: np.ndarray | None = None,
        dev_u: np.ndarray | None = None,
        node_cap: np.ndarray | None = None,
        reset_warm: bool = True,
    ) -> None:
        """Swap same-shape topology arrays on the pinned compiled program.

        The fleet topology is a *traced* argument of the engine step, so
        replacing device boxes or node capacities re-pins the engine without
        recompiling — the cheap path for supply-scale changes, coordinator
        budget grants, and device join/leave (a left device gets a
        zero-width ``[0, 0]`` box).  Shape or static-metadata changes still
        need a new engine.  Feasibility (caps >= subtree minimum draw) is
        revalidated on the host.  ``reset_warm`` drops carried duals — keep
        it for geometry changes; per-step budget grants may carry
        (``reset_warm=False``).
        """
        fleet = self.fleet
        with self._ctx():
            if node_cap is not None:
                node_cap = np.asarray(node_cap, np.float64)
                if node_cap.shape != (self.pdn.m,):
                    raise ValueError(
                        f"node_cap shape {node_cap.shape} != ({self.pdn.m},)"
                    )
                fleet = fleet._replace(
                    tree=fleet.tree._replace(cap=jnp.asarray(node_cap, self.dtype))
                )
            if dev_l is not None:
                dev_l = np.asarray(dev_l, np.float64)
                if dev_l.shape != (self.n,):
                    raise ValueError(f"dev_l shape {dev_l.shape} != ({self.n},)")
                fleet = fleet._replace(l=jnp.asarray(dev_l, self.dtype))
            if dev_u is not None:
                dev_u = np.asarray(dev_u, np.float64)
                if dev_u.shape != (self.n,):
                    raise ValueError(f"dev_u shape {dev_u.shape} != ({self.n},)")
                fleet = fleet._replace(u=jnp.asarray(dev_u, self.dtype))
        l_np = np.asarray(fleet.l, np.float64)
        u_np = np.asarray(fleet.u, np.float64)
        if (l_np < 0).any() or (l_np > u_np + 1e-12).any():
            raise ValueError("device limits must satisfy 0 <= l <= u")
        cap_np = np.asarray(fleet.tree.cap, np.float64)
        lmin = check_caps_fund_minimums(
            self.pdn.node_start, self.pdn.node_end, cap_np, l_np,
            what="re-pinned node",
        )
        self.fleet = fleet
        self._node_cap_np = cap_np
        self._subtree_lmin = lmin
        if reset_warm:
            self.reset_warm()

    def set_root_cap(self, cap: float, *, reset_warm: bool = False) -> None:
        """Re-pin only the root node's capacity — the coordinator's per-step
        budget grant in fleet mode.  Carries warm state by default (the
        solver duals track the drifting budget well).

        This is on the fleet orchestrator's per-step hot path, so it skips
        :meth:`repin`'s full O(n + m) revalidation: only the root row can
        change, and the cached subtree minimum bounds it from below.
        """
        cap = float(cap)
        if cap < self._subtree_lmin[0] - 1e-9:
            raise ValueError(
                f"root cap {cap:.1f} W < sum of device minimums "
                f"{self._subtree_lmin[0]:.1f} W"
            )
        self._node_cap_np = self._node_cap_np.copy()
        self._node_cap_np[0] = cap
        with self._ctx():
            self.fleet = self.fleet._replace(
                tree=self.fleet.tree._replace(
                    cap=jnp.asarray(self._node_cap_np, self.dtype)
                )
            )
        if reset_warm:
            self.reset_warm()

    def set_sla_bounds(
        self,
        lo: np.ndarray,
        hi: np.ndarray,
        *,
        reset_warm: bool = False,
    ) -> None:
        """Re-pin the tenant SLA aggregate bounds on the pinned program.

        The fleet coordinator's per-step hot path for cross-cut tenant
        sub-budgets: bounds are traced values (the incidence structure is
        static), so grants change with zero recompiles.  Carries warm state
        by default — the SLA duals track drifting sub-budgets well.
        """
        lo = np.asarray(lo, np.float64)
        hi = np.asarray(hi, np.float64)
        k = int(self.fleet.sla.lo.shape[0])
        if lo.shape != (k,) or hi.shape != (k,):
            raise ValueError(f"sla bounds shapes {lo.shape}/{hi.shape} != ({k},)")
        if (lo > hi + 1e-9).any():
            raise ValueError("sla bounds must satisfy lo <= hi")
        if self.meta.pin_free and (lo > 0).any():
            # the compiled program pins free devices at l (paper 4.3.1),
            # which is unsound once a tenant minimum can force them upward
            raise ValueError(
                "engine was compiled with the pin-free simplification "
                "(no positive SLA lower bounds at construction); rebuild "
                "the engine to raise tenant minimums above zero"
            )
        with self._ctx():
            self.fleet = self.fleet.with_sla_bounds(lo, hi, self.dtype)
        if reset_warm:
            self.reset_warm()

    def rescale_supply(self, scale: float, *, reset_warm: bool = True) -> None:
        """Scale all node capacities to ``scale`` x their construction-time
        values (absolute, not compounding) on the pinned program."""
        self.repin(node_cap=self._node_cap0 * float(scale), reset_warm=reset_warm)

    # -- host-side request pre-processing (numpy, O(n)) --------------------

    def _preprocess(self, telemetry, active):
        req = np.asarray(telemetry, dtype=np.float64)
        if req.shape[-1] != self.n:
            raise ValueError(f"telemetry shape {req.shape} != (..., {self.n})")
        if active is None:
            active = req >= self.idle_threshold
        return req, np.asarray(active, dtype=bool)

    # -- deadline calibration ----------------------------------------------

    def _budget(self, deadline_s):
        if deadline_s is _UNSET:
            deadline_s = self.options.deadline_s
        if deadline_s is None:
            return None
        if self._cost_model is None:
            self._cost_model = self._calibrate()
        # price the budget with the phase mix actually served, not the
        # calibration probe's: the engine's last step is the best predictor
        # of the next (ROADMAP per-phase deadline-calibration item)
        mix = None
        if self.history:
            pi = self.history[-1].get("phase_iterations")
            if pi and sum(pi) > 0:
                tot = float(sum(pi))
                mix = (pi[0] / tot, (pi[1] + pi[2]) / tot)
        return self._cost_model.budget(float(deadline_s), mix)

    def _calibrate(self) -> PhaseCostModel:
        """Per-phase seconds per PDHG iteration of this engine's compiled
        step (:class:`repro.core.batched.PhaseCostModel`).

        Times a Phase-I-only probe (budget 1) and a full-solve probe on
        neutral telemetry, compile excluded.  Like
        :func:`repro.core.batched.calibrate_phase_cost` the estimates
        include per-solve overhead, so deadline budgets err short.
        """
        tele = np.asarray(self.pdn.dev_u, np.float64)
        req, act = self._preprocess(tele, None)

        def probe(budget: int):
            with self._ctx():
                args = (
                    self.fleet,
                    jnp.asarray(req, self.dtype),
                    self.priority,
                    jnp.asarray(act),
                    None,
                    jnp.asarray(budget, jnp.int32),
                    None,
                )
                out = _engine_step_jit(
                    *args, meta=self.meta, opts=self.options.solver
                )
                out[2].block_until_ready()
                t0 = time.perf_counter()
                out = _engine_step_jit(
                    *args, meta=self.meta, opts=self.options.solver
                )
                out[2].block_until_ready()
                wall = time.perf_counter() - t0
            return wall, [int(out[4][f"iterations_p{i}"]) for i in (1, 2, 3)]

        wall1, phases1 = probe(1)
        wall_f, phases_f = probe(_PROBE_FULL_BUDGET)
        return PhaseCostModel.fit(wall1, phases1, wall_f, phases_f)

    # -- single-scenario control step --------------------------------------

    def step(
        self,
        telemetry: np.ndarray,
        *,
        active: np.ndarray | None = None,
        deadline_s: float | None = _UNSET,  # type: ignore[assignment]
    ) -> AllocResult:
        """One control step: telemetry [n] watts -> allocation (caps).

        Zero rebuild work: the only host-side cost is the O(n) request
        pre-processing and the telemetry/active transfer; everything else is
        one compiled program, warm-started from the previous step.

        ``wall_time_s`` of the result is the whole step on the host clock,
        pre-processing through the stats fetch.  With
        :mod:`repro.obs.spans` enabled the step records ``engine.step``
        holding ``engine.prepare`` (pre-processing, deadline budget,
        recorder init), ``engine.upload`` (telemetry and active mask to the
        device), ``engine.dispatch`` (the compiled program's call),
        ``engine.wait`` (until the caps are computed), ``engine.fetch``
        (the three allocations to host numpy) and ``engine.stats``.
        """
        with spans.span("engine.step"):
            t0 = time.perf_counter()
            with spans.span("engine.prepare"):
                req, act = self._preprocess(telemetry, active)
                budget = self._budget(deadline_s)
                # the incremental anchor is a traced input: skip/solve
                # transitions share one program
                inc = self._inc_carry if self.options.incremental else None
                if self._rec_cfg is not None and self._rec_state is None:
                    with self._ctx():
                        self._rec_state = obs_recorder.init_state(
                            self._rec_cfg, self.n, self.dtype
                        )
            with self._ctx():
                with spans.span("engine.upload"):
                    r_dev = jnp.asarray(req, self.dtype)
                    act_dev = jnp.asarray(act)
                    budget_dev = (
                        None if budget is None else jnp.asarray(budget, jnp.int32)
                    )
                # None (cold) and carry (steady) are two jit variants; the
                # cold one must stay warm=None so its phase chaining is
                # that of a cold nvpax.optimize call.
                with spans.span("engine.dispatch"):
                    x1, x2, x3, solver, stats, new_carry, new_rec = _engine_step_jit(
                        self.fleet,
                        r_dev,
                        self.priority,
                        act_dev,
                        self._warm,
                        budget_dev,
                        inc,
                        self._rec_state,
                        meta=self.meta,
                        opts=self.options.solver,
                        rec_cfg=self._rec_cfg,
                    )
                with spans.span("engine.wait"):
                    x3 = x3.block_until_ready()
            self._warm = solver
            if self.options.incremental:
                self._inc_carry = new_carry
            if self._rec_cfg is not None:
                self._rec_state = new_rec
            with spans.span("engine.fetch"):
                allocation, phase1, phase2 = (
                    np.asarray(x3), np.asarray(x1), np.asarray(x2)
                )
            with spans.span("engine.stats"):
                step_stats = StepStats.from_jit(stats, scalar=True, iter_budget=budget)
                wall = time.perf_counter() - t0
                self.history.append(
                    {
                        "wall_s": wall,
                        "converged": step_stats["converged"],
                        "solves": step_stats["total_solves"],
                        "iterations": step_stats["total_iterations"],
                        "phase_iterations": step_stats["phase_iterations"],
                        "waterfill_rounds": step_stats["waterfill_rounds"],
                        "waterfill_levels": step_stats["waterfill_levels"],
                        "project_steps_p1": step_stats["project_steps_p1"],
                        "project_levels_p1": step_stats["project_levels_p1"],
                        "truncated": step_stats["truncated"],
                        "skipped": step_stats["skipped"],
                    }
                )
        return AllocResult(
            allocation=allocation,
            phase1=phase1,
            phase2=phase2,
            warm_state=solver,
            wall_time_s=wall,
            carry=new_carry if self.options.incremental else None,
            stats=step_stats,
        )

    # -- batched control step ----------------------------------------------

    def step_batched(
        self,
        telemetry_batch: np.ndarray,
        *,
        active: np.ndarray | None = None,
        carry_warm: bool = True,
    ) -> BatchedAllocResult:
        """K scenarios in one compiled program, warm-carried across steps.

        ``telemetry_batch`` is ``[K, n]`` watts; ``active`` is ``[n]``
        (shared placement) or ``[K, n]``.  The batched solver state is
        carried per batch size K across consecutive calls (``carry_warm``),
        which cuts mean solver iterations on slowly-drifting telemetry;
        disable it for independent what-if sweeps.  ``options.deadline_s``
        is honored via the batched iteration-budget mode.
        """
        tb = np.asarray(telemetry_batch, dtype=np.float64)
        if tb.ndim != 2 or tb.shape[0] == 0:
            raise ValueError(
                f"telemetry_batch must be [K, n] with K >= 1, got {tb.shape}"
            )
        K, n = tb.shape
        if n != self.n:
            raise ValueError(f"telemetry_batch n {n} != fleet n {self.n}")
        if active is not None:
            active = np.asarray(active, bool)
            if active.shape == (n,):
                active = np.broadcast_to(active, (K, n))
            elif active.shape != (K, n):
                raise ValueError(
                    f"active must be [{n}] or [{K}, {n}], got {active.shape}"
                )
        req, act = self._preprocess(tb, active)
        with self._ctx():
            fl = self.fleet
            act_dev = jnp.asarray(act)
            r = _shape_requests(jnp.asarray(req, self.dtype), act_dev, fl.l, fl.u)
            stacked = AllocProblem(
                l=jnp.broadcast_to(fl.l, (K, n)),
                u=jnp.broadcast_to(fl.u, (K, n)),
                r=r,
                priority=jnp.broadcast_to(self.priority, (K, n)),
                active=act_dev,
                tree=fl.tree,
                sla=fl.sla,
                weight_scale=jnp.broadcast_to(fl.weight_scale, (K, n)),
            )
            if self._rec_cfg is not None and K not in self._rec_batched:
                self._rec_batched[K] = obs_recorder.init_batch(
                    self._rec_cfg, K, n, self.dtype
                )
            res = optimize_batched(
                stacked,
                self.options,
                warm=self._batched_warm.get(K) if carry_warm else None,
                meta=self.meta,
                carry=(
                    self._inc_batched_carry.get(K)
                    if self.options.incremental and carry_warm
                    else None
                ),
                rec=self._rec_batched.get(K),
                rec_cfg=self._rec_cfg,
            )
        if carry_warm:
            self._batched_warm[K] = res.warm_state
            if self.options.incremental:
                self._inc_batched_carry[K] = res.carry
        if self._rec_cfg is not None and res.recorder is not None:
            self._rec_batched[K] = res.recorder
        return res
