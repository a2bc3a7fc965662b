"""Multi-domain fleet orchestrator: one allocation engine per power domain,
coordinated by an inter-domain budget planner.

:class:`FleetOrchestrator` is the fleet-scale serving shape of the
allocator (ROADMAP "engine lifecycle at fleet scale").  The monolithic
:class:`repro.core.engine.AllocEngine` solves the whole datacenter as one
program; the orchestrator cuts the PDN at a chosen level
(:func:`repro.fleet.partition.split_pdn`) and runs the control step as a
two-level hierarchical solve:

1. the :class:`repro.fleet.coordinator.BudgetCoordinator` turns per-domain
   aggregate demand into per-domain budget grants, respecting every
   capacity row above the cut (waterfill on the coordinator tree);
2. each domain solves its own three-phase problem with its grant as the
   domain root capacity.

Per-domain solves dispatch in one of two modes:

* ``stacked`` — all K domains padded to a common ``(N, M)`` shape and
  solved as ONE jitted+vmapped ``solve_three_phase`` program.  The domain
  topology arrays (tree ranges, capacities, device boxes) are *traced*
  inputs, so per-step budget grants, supply derating, device join/leave,
  and even same-shape structural rebuilds of a single domain re-pin arrays
  without recompiling anything (see :func:`trace_count`);
* ``loop`` — one persistent :class:`AllocEngine` per domain, stepped in
  sequence.  Engines over the same geometry share one compiled executable
  (the engine jit cache is process-wide), and a structural rebuild of one
  domain never touches the other K-1 engines' compilations.

``mode="auto"`` picks ``stacked`` when the domains are homogeneous enough
that padding waste is small, else ``loop``.

Warm starts are carried per domain in both modes (a batched
:class:`repro.core.phases.WarmCarry` with ``[K, ...]`` leaves, or each
engine's own carry); churn resets only the affected domain's carry.

**Tenant SLAs** (``tenants=`` at construction) work across the cut: the
partition classifies tenants as domain-local (their contractual row is an
ordinary SLA box inside one domain) or *cross-cut* (devices in several
domains).  Every step the coordinator splits each cross-cut tenant's
``[b_min, b_max]`` into per-domain slice sub-budgets
(:meth:`BudgetCoordinator.plan_sla`), raises the domain grant floors so
every feed funds its share of the tenant minimums, and the orchestrator
threads the sub-budgets into the per-domain solves as traced SLA rows —
stacked and loop dispatch alike, so grant changes and churn re-pins still
recompile nothing (asserted via :func:`trace_count` in
``tests/test_fleet_sla.py``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import phases, solver
from repro.core.batched import BatchMeta, solve_three_phase
from repro.core.engine import AllocEngine, _shape_requests
from repro.core.nvpax import NvpaxOptions
from repro.core.problem import AllocProblem
from repro.core.treeops import SlaTopo, TreeTopo
from repro.fleet.coordinator import (
    BudgetCoordinator,
    check_tenants_deliverable,
    split_entitlements,
)
from repro.fleet.partition import (
    FleetPartition,
    FleetSla,
    build_fleet_sla,
    split_pdn,
)
from repro.obs import recorder as obs_recorder
from repro.obs import spans
from repro.obs.stats import StepStats
from repro.pdn.tree import FlatPDN, check_caps_fund_minimums

__all__ = ["FleetOrchestrator", "FleetStepResult", "trace_count"]

# stacked-dispatch retrace counter (see repro.core.engine.trace_count for
# the per-domain engine loop's counter)
_N_TRACES = 0


def trace_count() -> int:
    """Times the stacked fleet program has been traced in this process."""
    return _N_TRACES


class _DomainBatch(NamedTuple):
    """[K, ...] padded per-domain fleet arrays (all traced; caps and tenant
    SLA bounds travel separately because they change every step with the
    coordinator grants)."""

    l: jnp.ndarray  # [K, N]
    u: jnp.ndarray  # [K, N]
    weight_scale: jnp.ndarray  # [K, N]
    priority: jnp.ndarray  # [K, N] int32
    start: jnp.ndarray  # [K, M] int32
    end: jnp.ndarray  # [K, M] int32
    depth: jnp.ndarray  # [K, M] int32
    sla_dev: jnp.ndarray  # [K, E] int32 (padded edges -> the inert pad row)
    sla_ten: jnp.ndarray  # [K, E] int32


def _record_domains(cfg, rec, stats, alloc, dom, sla_lo, r, active):
    """Per-domain flight-record append (vmapped over the lane axis; under
    shard_map each shard records its own lanes with no collectives)."""
    nrows = int(sla_lo.shape[1])

    def one(rec_k, st_k, a, l, u, sdev, sten, slo, r_k, act_k):
        r_eff = jnp.where(act_k, jnp.clip(r_k, l, u), 0.0)
        margin = obs_recorder.sla_min_margin(a, sdev, sten, slo, nrows)
        m = obs_recorder.step_metrics(st_k, a, r_eff, margin)
        return obs_recorder.record_step(cfg, rec_k, m, a)

    return jax.vmap(one)(
        rec, stats, alloc, dom.l, dom.u, dom.sla_dev, dom.sla_ten,
        sla_lo, r, active,
    )


def _solve_domains(
    dom, cap, sla_lo, sla_hi, r, active, warm, carry=None, rec=None,
    *, meta, opts, rec_cfg=None,
):
    """The vmapped per-domain three-phase solve over [K, ...] arrays.

    Shared body of the stacked dispatch (:func:`_fleet_solve`) and the
    sharded dispatch (:mod:`repro.fleet.sharded`, where K is the per-shard
    domain count) so both modes trace the identical per-domain program.

    ``carry`` (incremental mode, with ``[K, ...]`` leaves) threads each
    domain's :class:`repro.core.solver.certify.IncrementalCarry` anchor
    into the per-domain solve: dirty domains iterate, clean domains are
    frozen by the while-loop batching rule, and when *every* domain in the
    batch certifies a full skip a scalar ``lax.cond`` short-circuits the
    whole vmapped solve to the O(matvec) assembly below.  In the sharded
    dispatch each shard takes that branch independently (no collectives on
    either side of the cond).

    ``rec``/``rec_cfg`` (flight recorder, PR 8) thread per-domain
    :class:`repro.obs.recorder.RecorderState` pytrees; recording happens
    after the all-skip cond so both branches log.  Returns ``(x1, x2, x3,
    warm_carry, stats, new_carry, rec)``.
    """

    def build_problem(l, u, ws, pri, start, end, depth, sdev, sten,
                      cap_k, slo_k, shi_k, r_k, act_k):
        tree = TreeTopo(start=start, end=end, cap=cap_k, depth=depth)
        sla = SlaTopo(dev=sdev, ten=sten, lo=slo_k, hi=shi_k)
        return AllocProblem(
            l=l,
            u=u,
            r=_shape_requests(r_k, act_k, l, u),
            priority=pri,
            active=act_k,
            tree=tree,
            sla=sla,
            weight_scale=ws,
        )

    def one(*args):
        warm_k, carry_k = args[-2], args[-1]
        ap = build_problem(*args[:-2])
        x1, x2, x3, wc, stats = solve_three_phase(
            ap, meta, opts, warm_k, None, carry_k
        )
        new_carry = solver.update_carry(
            carry_k,
            ap,
            x1,
            x2,
            x3,
            stats["skipped"],
            stats["certify_pass"] & ~stats["skipped"],
        )
        return x1, x2, x3, wc, stats, new_carry

    dom_leaves = (
        dom.l,
        dom.u,
        dom.weight_scale,
        dom.priority,
        dom.start,
        dom.end,
        dom.depth,
        dom.sla_dev,
        dom.sla_ten,
        cap,
        sla_lo,
        sla_hi,
        r,
        active,
    )
    warm_axes = None if warm is None else 0

    def run_vmapped(c):
        return jax.vmap(one, in_axes=(0,) * 14 + (warm_axes, None if c is None else 0))(
            *dom_leaves, warm, c
        )

    def finish(out):
        x1, x2, x3, wc, stats, new_carry = out
        new_rec = rec
        if rec is not None and rec_cfg is not None:
            new_rec = _record_domains(
                rec_cfg, rec, stats, x3, dom, sla_lo, r, active
            )
        return x1, x2, x3, wc, stats, new_carry, new_rec

    if carry is None or warm is None:
        # no anchor yet (or no warm state to thread through the all-skip
        # assembly): per-lane gating alone
        return finish(run_vmapped(carry))

    def cert_one(*args):
        ap = build_problem(*args[:-1])
        return solver.certify_step(
            ap,
            args[-1],
            meta.n_depths,
            tol=meta.certify_tol,
            margin=meta.certify_margin,
            opts=opts,
        )

    dec = jax.vmap(cert_one, in_axes=(0,) * 14 + (0,))(*dom_leaves, carry)
    kk = dom.l.shape[0]

    def fast(_):
        # every domain certified: assemble the exact all-skip outputs the
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
            "kkt_res": jnp.zeros((kk,), dom.l.dtype),
            "restarts": zi,
            "kkt_hist": jnp.zeros(
                (kk, solver.KKT_HIST_BUCKETS), jnp.int32
            ),
        }
        wcarry = phases.WarmCarry(p1_sol, w2, w3)
        return carry.x1, carry.x2, dec.x_snap, wcarry, stats, carry

    def slow(_):
        return run_vmapped(carry)

    return finish(jax.lax.cond(jnp.all(dec.skip), fast, slow, None))


def _fleet_solve(
    dom, cap, sla_lo, sla_hi, r, active, warm, carry=None, rec=None,
    *, meta, opts, rec_cfg=None,
):
    """All K domain control steps as one traced program."""
    global _N_TRACES
    _N_TRACES += 1  # executes at trace time only
    return _solve_domains(
        dom, cap, sla_lo, sla_hi, r, active, warm, carry, rec,
        meta=meta, opts=opts, rec_cfg=rec_cfg,
    )


_fleet_step_jit = jax.jit(
    _fleet_solve, static_argnames=("meta", "opts", "rec_cfg")
)


@dataclasses.dataclass
class FleetStepResult:
    """One fleet control step: global allocation + coordinator decisions."""

    allocation: np.ndarray  # [n] global device order (domain concatenation)
    phase1: np.ndarray  # [n] Phase I caps, same order
    phase2: np.ndarray  # [n] Phase II caps, same order
    grants: np.ndarray  # [K] coordinator budget grants (watts)
    demand: np.ndarray  # [K] per-domain aggregate shaped demand (watts)
    wall_time_s: float
    stats: dict[str, Any]  # per-domain solves/iterations/converged arrays


class FleetOrchestrator:
    """Construct-once / step-many fleet runtime over K power domains.

    Parameters
    ----------
    pdn : the full datacenter tree.
    level : cut depth; every node at this depth roots one domain.
    mode : ``"auto"`` | ``"stacked"`` | ``"loop"`` (see module docstring).
    coordinator_mode : budget policy, see
        :class:`repro.fleet.coordinator.BudgetCoordinator`.
    tenants : optional tenant SLA layout (anything with
        ``tenant_of``/``b_min``/``b_max``, e.g.
        :class:`repro.pdn.tenants.TenantLayout`); tenants may span the
        domain cut (see module docstring).  ``priority`` defaults to the
        layout's priorities when it carries them.
    pad_factor : in ``auto`` mode, use the stacked dispatch when padding
        every domain to the largest one wastes at most this factor in both
        device and node counts.
    """

    def __init__(
        self,
        pdn: FlatPDN,
        *,
        level: int = 1,
        options: NvpaxOptions | None = None,
        priority: np.ndarray | None = None,
        tenants=None,
        idle_threshold: float = 150.0,
        coordinator_mode: str = "waterfill",
        mode: str = "auto",
        pad_factor: float = 2.0,
        dtype=jnp.float64,
        recorder: obs_recorder.RecorderConfig | bool | None = None,
    ):
        self.partition: FleetPartition = split_pdn(pdn, level, tenants=tenants)
        self._sla: FleetSla | None = self.partition.sla
        self.coordinator = BudgetCoordinator(self.partition, mode=coordinator_mode)
        self.options = options or NvpaxOptions()
        self.idle_threshold = float(idle_threshold)
        self.dtype = dtype
        self._x64 = bool(self.options.x64) and dtype == jnp.float64
        K = self.partition.k
        if priority is None and tenants is not None:
            priority = getattr(tenants, "priority", None)
        if priority is None:
            priority = np.ones((pdn.n,), np.int32)
        priority = np.asarray(priority, np.int32)
        if priority.shape != (pdn.n,):
            raise ValueError(f"priority shape {priority.shape} != ({pdn.n},)")
        if (priority < 1).any():
            raise ValueError("priorities must be >= 1")
        # mutable per-domain state (survives churn/rebuilds; global device
        # order is always the domain concatenation in domain index order)
        self._local_pdn: list[FlatPDN] = [d.pdn for d in self.partition.domains]
        self._priority: list[np.ndarray] = [
            priority[d.dev_lo : d.dev_hi].copy() for d in self.partition.domains
        ]
        self._dev_l: list[np.ndarray] = [p.dev_l.copy() for p in self._local_pdn]
        self._dev_u: list[np.ndarray] = [p.dev_u.copy() for p in self._local_pdn]
        self._node_cap: list[np.ndarray] = [p.node_cap.copy() for p in self._local_pdn]
        self._domain_supply = np.ones(K)
        self._feed_scale = 1.0
        if mode == "auto":
            ns = np.array([p.n for p in self._local_pdn])
            ms = np.array([p.m for p in self._local_pdn])
            homogeneous = (
                ns.max() <= pad_factor * ns.min()
                and ms.max() <= pad_factor * ms.min()
            )
            mode = "stacked" if homogeneous else "loop"
        if mode not in ("stacked", "loop", "sharded"):
            raise ValueError(f"mode must be auto/stacked/loop/sharded, got {mode!r}")
        if mode == "sharded" and coordinator_mode not in ("waterfill", "subtree"):
            raise ValueError(
                "sharded dispatch supports waterfill/subtree coordinators, "
                f"got {coordinator_mode!r}"
            )
        self.mode = mode
        self._mesh = None
        if mode == "sharded":
            from repro.fleet import sharded as _sharded

            self._mesh = _sharded.build_mesh(K)
        self._engines: list[AllocEngine] | None = None
        self._warm: phases.WarmCarry | None = None
        # incremental mode (options.incremental): stacked/sharded keep a
        # batched certify anchor ([K, ...] leaves); loop mode keeps the host
        # anchor of the dirty-domain dispatch (frozen per-domain allocations
        # plus the demand/grant/telemetry values they were solved against)
        self._inc_carry: Any = None
        self._loop_prev: dict[str, Any] | None = None
        self.history: list[dict[str, Any]] = []
        # flight recorder (PR 8): stacked/sharded keep one [K, ...]-leaf
        # state threaded through the jitted step; loop mode delegates to
        # each domain engine's own recorder (built below)
        if recorder is True:
            recorder = obs_recorder.RecorderConfig()
        self._rec_cfg: obs_recorder.RecorderConfig | None = recorder or None
        self._rec_state: obs_recorder.RecorderState | None = None
        if self._sla is not None:
            # fail fast: contracts must be deliverable and fundable under
            # the nameplate feeds before the first step
            self._check_effective_floors()
        if mode in ("stacked", "sharded"):
            # pad to the largest domain; static metadata is the union over
            # domains so per-domain differences stay traced, never static
            self._N = int(max(p.n for p in self._local_pdn))
            self._M = int(max(p.m for p in self._local_pdn))
            # SLA pads: one extra always-inert row receives the padded
            # incidence edges, so every real row keeps exact semantics
            self._E = self._sla.max_edges if self._sla is not None else 0
            self._T = self._sla.max_rows + 1 if self._sla is not None else 0
            self.meta = BatchMeta(
                levels=tuple(sorted({int(p) for p in priority}, reverse=True)),
                n_depths=int(max(p.node_depth.max() for p in self._local_pdn)) + 1,
                # tenant minimums can force pinned-free devices upward, so
                # the pin-free simplification (paper 4.3.1) is SLA-free only
                pin_free=self._sla is None,
                max_rounds=self.options.max_rounds,
                use_waterfill=self.options.use_waterfill,
                run_phase2=self.options.run_phase2,
                run_phase3=self.options.run_phase3,
                eps=self.options.eps,
            )
            self._upload()
        else:
            rb = self._initial_row_bounds() if self._sla is not None else None
            self._engines = [
                self._build_engine(k, p, rb)
                for k, p in enumerate(self._local_pdn)
            ]

    # -- geometry ----------------------------------------------------------

    @property
    def k(self) -> int:
        return self.partition.k

    @property
    def domain_sizes(self) -> np.ndarray:
        return np.array([p.n for p in self._local_pdn], np.int64)

    @property
    def n(self) -> int:
        """Current total device count (changes on structural rebuilds)."""
        return int(self.domain_sizes.sum())

    def _offsets(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.domain_sizes)])

    def device_bounds(self) -> np.ndarray:
        """[n] current global lower bounds (domain concatenation order)."""
        return np.concatenate(self._dev_l)

    def device_caps(self) -> np.ndarray:
        return np.concatenate(self._dev_u)

    # -- stacked-mode array management -------------------------------------

    def _ctx(self):
        return jax.enable_x64(True) if self._x64 else contextlib.nullcontext()

    def _upload(self) -> None:
        """(Re)build the padded [K, ...] device arrays from host mirrors."""
        K, N, M = self.k, self._N, self._M
        l = np.zeros((K, N))
        u = np.zeros((K, N))
        ws = np.ones((K, N))
        pri = np.ones((K, N), np.int32)
        start = np.full((K, M), N, np.int32)  # padded nodes: empty range
        end = np.full((K, M), N, np.int32)
        depth = np.zeros((K, M), np.int32)
        cap = np.full((K, M), np.inf)
        for k, p in enumerate(self._local_pdn):
            l[k, : p.n] = self._dev_l[k]
            u[k, : p.n] = self._dev_u[k]
            pri[k, : p.n] = self._priority[k]
            start[k, : p.m] = p.node_start
            end[k, : p.m] = p.node_end
            depth[k, : p.m] = p.node_depth
            cap[k, : p.m] = self._node_cap[k]
        self._cap_np = cap  # host mirror; row 0 gets the per-step grants
        # tenant SLA incidence, padded: extra edges point at the always-
        # inert pad row T-1 (bounds [0, inf) every step), so they never
        # constrain anything
        E, T = self._E, self._T
        sla_dev = np.zeros((K, E), np.int32)
        sla_ten = np.full((K, E), max(T - 1, 0), np.int32)
        if self._sla is not None:
            for k in range(K):
                dev, ten = self._sla.edges(k)
                sla_dev[k, : dev.shape[0]] = dev
                sla_ten[k, : ten.shape[0]] = ten
        with self._ctx():
            self._dom = _DomainBatch(
                l=jnp.asarray(l, self.dtype),
                u=jnp.asarray(u, self.dtype),
                weight_scale=jnp.asarray(ws, self.dtype),
                priority=jnp.asarray(pri),
                start=jnp.asarray(start),
                end=jnp.asarray(end),
                depth=jnp.asarray(depth),
                sla_dev=jnp.asarray(sla_dev),
                sla_ten=jnp.asarray(sla_ten),
            )
            if self._mesh is not None:
                # pin the persistent arrays to their mesh shards once, so
                # per-step dispatch moves only telemetry, not topology
                from repro.fleet import sharded as _sharded

                sh = _sharded.domain_sharding(self._mesh)
                self._dom = jax.tree_util.tree_map(
                    lambda a: jax.device_put(a, sh), self._dom
                )

    # -- tenant SLA plumbing -----------------------------------------------

    def _build_engine(self, k: int, p: FlatPDN, row_bounds=None) -> AllocEngine:
        """Loop-mode per-domain engine, with its local SLA structure.
        ``row_bounds`` (all domains' initial SLA bounds) avoids recomputing
        the entitlement split per engine when building K at once."""
        sla_topo = None
        if self._sla is not None and self._sla.n_rows(k):
            from repro.core.treeops import SlaTopo as _SlaTopo

            dev, ten = self._sla.edges(k)
            if row_bounds is None:
                row_bounds = self._initial_row_bounds()
            lo, hi = row_bounds[k]
            sla_topo = _SlaTopo(dev=dev, ten=ten, lo=lo, hi=hi)
        return AllocEngine(
            p,
            sla=sla_topo,
            priority=self._priority[k],
            options=self.options,
            idle_threshold=self.idle_threshold,
            # SLA lower bounds are re-pinned per step (tenant sub-budgets,
            # runtime grant changes) and may rise above zero later; the
            # pin-free simplification must stay off for SLA domains
            pin_free=False if sla_topo is not None else None,
            recorder=self._rec_cfg,
        )

    def _slice_aggregates(
        self,
        dev_l: list[np.ndarray],
        dev_u: list[np.ndarray],
        shaped: np.ndarray | None = None,
        sla: FleetSla | None = None,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Per-slice (floor, umax, demand) sums over the given boxes."""
        sla = sla or self._sla
        S = sla.n_slices
        sf = np.zeros(S)
        su = np.zeros(S)
        sd = np.zeros(S)
        offs = np.concatenate([[0], np.cumsum([l.shape[0] for l in dev_l])])
        for s in range(S):
            k = int(sla.slice_domain[s])
            idx = sla.row_dev[k][int(sla.slice_row[s])]
            sf[s] = dev_l[k][idx].sum()
            su[s] = dev_u[k][idx].sum()
            if shaped is not None:
                sd[s] = shaped[offs[k] : offs[k + 1]][idx].sum()
        return sf, su, sd

    def _local_lift(
        self,
        dev_l: list[np.ndarray],
        dev_u: list[np.ndarray],
        sla: FleetSla | None = None,
    ) -> np.ndarray:
        """[K] extra minimum draw from *domain-local* tenant minimums, with
        per-tenant deliverability validation (umax funds b_min, floors stay
        under b_max)."""
        sla = sla or self._sla
        lift = np.zeros(self.k)
        for k in range(self.k):
            for r, t in enumerate(sla.rows[k]):
                if sla.row_slice[k][r] >= 0:
                    continue
                idx = sla.row_dev[k][r]
                floor = float(dev_l[k][idx].sum())
                umax = float(dev_u[k][idx].sum())
                if umax < sla.b_min[t] - 1e-9:
                    raise ValueError(
                        f"tenant {int(t)} minimum {sla.b_min[t]:.1f} W exceeds "
                        f"its deliverable maximum {umax:.1f} W in domain {k}; "
                        "restore devices or relax the SLA"
                    )
                if floor > sla.b_max[t] + 1e-9:
                    raise ValueError(
                        f"tenant {int(t)} device floors {floor:.1f} W exceed "
                        f"its contractual maximum {sla.b_max[t]:.1f} W"
                    )
                lift[k] += max(float(sla.b_min[t]) - floor, 0.0)
        return lift

    def _sla_lifts(
        self,
        dev_l: list[np.ndarray],
        dev_u: list[np.ndarray],
        sla: FleetSla | None = None,
    ) -> np.ndarray:
        """[K] total tenant minimum-draw lift (local + cross-cut) under the
        given boxes.  The cross-cut part uses the demand-free entitlement
        split, which is exactly what the next ``plan_sla`` will enforce, so
        mutation-time validation and step-time behavior agree."""
        sla = sla or self._sla
        if sla is None:
            return np.zeros(self.k)
        # a tenant with a positive contractual minimum must own at least one
        # device somewhere — otherwise (e.g. a rebuild_domain that dropped
        # its last devices) the contract would go silently unenforced
        present = np.zeros(sla.n_tenants, bool)
        for rows in sla.rows:
            present[rows] = True
        orphan = np.nonzero(~present & (sla.b_min > 1e-12))[0]
        if orphan.size:
            t = int(orphan[0])
            raise ValueError(
                f"tenant {t} has a contractual minimum {sla.b_min[t]:.1f} W "
                "but no devices; relax the contract "
                "(set_tenant_bounds(b_min=0)) before removing its last "
                "devices"
            )
        lift = self._local_lift(dev_l, dev_u, sla)
        if sla.n_slices:
            sf, su, _ = self._slice_aggregates(dev_l, dev_u, sla=sla)
            check_tenants_deliverable(sla, sf, su)
            slice_lo, _ = split_entitlements(sla, sf, su, sf)
            np.add.at(lift, sla.slice_domain, slice_lo - sf)
        return lift

    def _sla_row_bounds(
        self,
        slice_lo: np.ndarray,
        slice_hi: np.ndarray,
        sla: FleetSla | None = None,
    ) -> list[tuple[np.ndarray, np.ndarray]]:
        """Per-domain SLA row bounds: contractual rows for domain-local
        tenants, coordinator sub-budgets for cross-cut slices."""
        sla = sla or self._sla
        out = []
        for k in range(self.k):
            R = sla.n_rows(k)
            lo = np.zeros(R)
            hi = np.zeros(R)
            for r, t in enumerate(sla.rows[k]):
                s = int(sla.row_slice[k][r])
                if s >= 0:
                    lo[r], hi[r] = slice_lo[s], slice_hi[s]
                else:
                    lo[r], hi[r] = sla.b_min[t], sla.b_max[t]
            out.append((lo, hi))
        return out

    def _initial_row_bounds(self) -> list[tuple[np.ndarray, np.ndarray]]:
        """Demand-free row bounds from current mirrors (construction and
        engine rebuilds; every step re-pins the real ones)."""
        sf, su, _ = self._slice_aggregates(self._dev_l, self._dev_u)
        slice_lo, slice_hi = split_entitlements(self._sla, sf, su, sf)
        return self._sla_row_bounds(slice_lo, slice_hi)

    def _tenant_of_list(self) -> list[np.ndarray]:
        """Per-domain local tenant membership, reconstructed from the
        layout (the inverse of ``build_fleet_sla``'s input)."""
        out = []
        for k in range(self.k):
            t_of = np.full(self._dev_l[k].shape[0], -1, np.int32)
            for r, t in enumerate(self._sla.rows[k]):
                t_of[self._sla.row_dev[k][r]] = t
            out.append(t_of)
        return out

    def set_tenant_bounds(
        self,
        tenant: int,
        *,
        b_min: float | None = None,
        b_max: float | None = None,
    ) -> None:
        """Change one tenant's contractual ``[b_min, b_max]`` at runtime.

        Pure coordinator-level state: the new bounds flow into the next
        step's entitlement split and per-domain SLA rows as traced values —
        nothing recompiles (asserted in ``tests/test_fleet_sla.py``).  The
        whole change is validated (deliverability, derated feeds still fund
        the shifted minimums) before any state is committed.
        """
        sla = self._sla
        if sla is None:
            raise ValueError("orchestrator was built without tenants")
        if not 0 <= int(tenant) < sla.n_tenants:
            raise ValueError(f"tenant {tenant} out of range [0, {sla.n_tenants})")
        new_min = sla.b_min.copy()
        new_max = sla.b_max.copy()
        if b_min is not None:
            new_min[tenant] = float(b_min)
        if b_max is not None:
            new_max[tenant] = float(b_max)
        if new_min[tenant] < 0 or new_min[tenant] > new_max[tenant] + 1e-9:
            raise ValueError("tenant bounds must satisfy 0 <= b_min <= b_max")
        candidate = dataclasses.replace(sla, b_min=new_min, b_max=new_max)
        self._check_effective_floors(sla=candidate)
        self._sla = candidate

    def _reset_domain_warm(self, k: int) -> None:
        if self.mode == "loop":
            if self._engines is not None:
                self._engines[k].reset_warm()
        elif self._warm is not None:
            with self._ctx():
                self._warm = jax.tree_util.tree_map(
                    lambda a: a.at[k].set(jnp.zeros_like(a[k])), self._warm
                )
        self._invalidate_incremental(k)

    def _invalidate_incremental(self, k: int) -> None:
        """Poison domain ``k``'s incremental anchor after a re-pin/rebuild:
        an infinite anchor demand fails every certify tier, forcing a full
        solve for that domain on the next step (the other K-1 anchors keep
        skipping)."""
        if self._inc_carry is not None:
            with self._ctx():
                self._inc_carry = self._inc_carry._replace(
                    r=self._inc_carry.r.at[k].set(jnp.inf)
                )
        if self._loop_prev is not None:
            self._loop_prev["alloc"][k] = None

    # -- lifecycle: supply + churn re-pins ---------------------------------

    def set_domain_supply(self, k: int, scale: float) -> None:
        """Derate (or restore) one domain's feed: the coordinator caps that
        domain's grant at ``scale`` x its subtree capacity from the next
        step on.  Pure coordinator state — nothing recompiles, and the
        freed budget is redistributed to the other domains.

        The derated feed must still fund the domain's current minimum draw
        (grants below it make the domain's own problem infeasible); for a
        deeper derate — including a full outage — mask devices out first
        (:meth:`repro.fleet.lifecycle.FleetLifecycle.device_leave`).
        ``scale`` is capped at 1.0: the PDN caps are physical limits, not a
        planning knob (1.0 restores the nameplate feed).
        """
        if not 0.0 <= scale <= 1.0:
            raise ValueError(f"scale must be in [0, 1], got {scale}")
        dcap_eff = np.array([c[0] for c in self._node_cap]) * self._domain_supply
        dcap_eff[k] = float(self._node_cap[k][0]) * float(scale)
        self._check_effective_floors(dcap_eff=dcap_eff)
        self._domain_supply[k] = float(scale)

    def set_feed_scale(self, scale: float) -> None:
        """Derate every capacity above the cut (utility feed event).  Like
        :meth:`set_domain_supply`, the derated rows must still fund the
        fleet's current minimum draw and ``scale`` cannot exceed 1.0."""
        if not 0.0 <= scale <= 1.0:
            raise ValueError(f"scale must be in [0, 1], got {scale}")
        self._check_effective_floors(feed_scale=float(scale))
        self._feed_scale = float(scale)

    def _check_effective_floors(
        self,
        dev_l: list[np.ndarray] | None = None,
        dev_u: list[np.ndarray] | None = None,
        dcap_eff: np.ndarray | None = None,
        feed_scale: float | None = None,
        sla: FleetSla | None = None,
    ) -> None:
        """The *derated* feeds (domain supplies + feed scale) must fund the
        per-domain minimum draws — device floors plus tenant minimum lifts —
        under the given (possibly prospective) boxes, derates and SLA
        bounds.  Shared by every mutation path (supply derates, box
        re-pins, rejoins, tenant grant changes) so a rejected change leaves
        all state untouched."""
        dev_l = self._dev_l if dev_l is None else dev_l
        dev_u = self._dev_u if dev_u is None else dev_u
        dmin = np.array([l.sum() for l in dev_l])
        dmin = dmin + self._sla_lifts(dev_l, dev_u, sla or self._sla)
        if dcap_eff is None:
            dcap_eff = np.array([c[0] for c in self._node_cap]) * self._domain_supply
        bad = np.nonzero(dmin > dcap_eff + 1e-9)[0]
        if bad.size:
            k = int(bad[0])
            raise ValueError(
                f"domain {k} minimum draw {dmin[k]:.1f} W exceeds its "
                f"derated feed {dcap_eff[k]:.1f} W; restore the supply "
                "(set_domain_supply) or mask devices out first "
                "(FleetLifecycle.device_leave)"
            )
        scale = self._feed_scale if feed_scale is None else feed_scale
        check_caps_fund_minimums(
            self.coordinator.start,
            self.coordinator.end,
            self.coordinator.cap * scale,
            dmin,
            what="derated coordinator row",
        )

    def repin_domain(
        self,
        k: int,
        *,
        dev_l: np.ndarray | None = None,
        dev_u: np.ndarray | None = None,
        node_cap: np.ndarray | None = None,
        reset_warm: bool = True,
    ) -> None:
        """Swap same-shape arrays of ONE domain (device join/leave masks,
        cap trims).  The other K-1 domains' compiled work is untouched in
        both modes; in stacked mode nothing recompiles at all.

        The whole re-pin is validated (box ordering, caps >= subtree
        minimum draw — the same checks as ``AllocEngine.repin``) before any
        orchestrator state changes, so a rejected re-pin leaves mirrors,
        engines and device arrays consistent.
        """
        p = self._local_pdn[k]
        new_l = self._dev_l[k] if dev_l is None else np.asarray(dev_l, np.float64)
        new_u = self._dev_u[k] if dev_u is None else np.asarray(dev_u, np.float64)
        new_cap = (
            self._node_cap[k] if node_cap is None
            else np.asarray(node_cap, np.float64)
        )
        if new_l.shape != (p.n,) or new_u.shape != (p.n,):
            raise ValueError(
                f"dev_l/dev_u shapes {new_l.shape}/{new_u.shape} != ({p.n},)"
            )
        if new_cap.shape != (p.m,):
            raise ValueError(f"node_cap shape {new_cap.shape} != ({p.m},)")
        if (new_l < 0).any() or (new_l > new_u + 1e-12).any():
            raise ValueError("device limits must satisfy 0 <= l <= u")
        check_caps_fund_minimums(
            p.node_start,
            p.node_end,
            new_cap,
            new_l,
            what=f"domain {k} node",
        )
        # an active derate must also still fund the (possibly raised) floor
        # — including tenant minimum lifts — otherwise the failure would
        # surface one step later in plan()
        dev_l_new = list(self._dev_l)
        dev_u_new = list(self._dev_u)
        dev_l_new[k] = new_l
        dev_u_new[k] = new_u
        dcap_eff = np.array([c[0] for c in self._node_cap]) * self._domain_supply
        dcap_eff[k] = new_cap[0] * self._domain_supply[k]
        self._check_effective_floors(
            dev_l=dev_l_new, dev_u=dev_u_new, dcap_eff=dcap_eff
        )
        self._dev_l[k] = new_l.copy()
        self._dev_u[k] = new_u.copy()
        self._node_cap[k] = new_cap.copy()
        if self.mode == "loop":
            assert self._engines is not None
            # always pass the nameplate caps: the engine's live root cap
            # still holds the previous step's coordinator grant, which
            # could spuriously fail a join that the next grant would fund
            # (the grant is re-applied by set_root_cap on the next step)
            self._engines[k].repin(
                dev_l=new_l,
                dev_u=new_u,
                node_cap=new_cap,
                reset_warm=reset_warm,
            )
            self._invalidate_incremental(k)
        else:
            # update only row k (O(N) host work + one-row transfers); the
            # full K-domain rebuild is reserved for structural rebuilds
            if dev_l is not None or dev_u is not None:
                row_l = np.zeros(self._N)
                row_u = np.zeros(self._N)
                row_l[: p.n] = self._dev_l[k]
                row_u[: p.n] = self._dev_u[k]
                with self._ctx():
                    self._dom = self._dom._replace(
                        l=self._dom.l.at[k].set(jnp.asarray(row_l, self.dtype)),
                        u=self._dom.u.at[k].set(jnp.asarray(row_u, self.dtype)),
                    )
            if node_cap is not None:
                self._cap_np[k, : p.m] = self._node_cap[k]
            if reset_warm:
                self._reset_domain_warm(k)
        if not reset_warm:
            # the certify anchors compare boxes/caps and would catch the
            # re-pin anyway; poisoning keeps the frozen-allocation paths
            # trivially sound without relying on that comparison
            self._invalidate_incremental(k)

    def rebuild_domain(
        self,
        k: int,
        new_pdn: FlatPDN,
        *,
        priority: np.ndarray | None = None,
        tenant_of: np.ndarray | None = None,
    ) -> None:
        """Replace one domain's topology (structural churn: servers added or
        decommissioned).  Only this domain's engine is rebuilt; the other
        K-1 domains keep their compiled programs and warm state.  In stacked
        mode the new topology must fit the padded shape and static metadata
        (device/node counts, tree depth, priority levels, SLA row/edge
        counts); it then re-pins as traced arrays with zero recompilation.

        ``tenant_of`` maps the new domain's local devices to global tenant
        ids (-1 unassigned; default: the rebuilt domain carries no tenant
        devices).  Cross-cut tenant membership is updated atomically with
        the topology: the whole change — shapes, tenant deliverability
        under the new boxes, derated feeds funding the shifted minimum
        lifts — is validated before any state is committed, and a tenant
        whose devices now all live in one domain reverts to an ordinary
        domain-local SLA row.
        """
        new_pdn.validate()
        if priority is None:
            priority = np.ones((new_pdn.n,), np.int32)
        priority = np.asarray(priority, np.int32)
        if priority.shape != (new_pdn.n,):
            raise ValueError(f"priority shape {priority.shape} != ({new_pdn.n},)")
        candidate_sla = self._sla
        if self._sla is not None:
            if tenant_of is None:
                tenant_of = np.full(new_pdn.n, -1, np.int32)
            tenant_of = np.asarray(tenant_of, np.int32)
            if tenant_of.shape != (new_pdn.n,):
                raise ValueError(f"tenant_of shape {tenant_of.shape} != ({new_pdn.n},)")
            lists = self._tenant_of_list()
            lists[k] = tenant_of
            candidate_sla = build_fleet_sla(lists, self._sla.b_min, self._sla.b_max)
        elif tenant_of is not None:
            raise ValueError("orchestrator was built without tenants")
        if self.mode == "stacked":
            if new_pdn.n > self._N or new_pdn.m > self._M:
                raise ValueError(
                    f"domain {k} rebuild ({new_pdn.n} devices, {new_pdn.m} "
                    f"nodes) exceeds the padded shape ({self._N}, {self._M}); "
                    "rebuild the orchestrator"
                )
            if int(new_pdn.node_depth.max()) + 1 > self.meta.n_depths:
                raise ValueError("rebuild deepens the tree; rebuild the orchestrator")
            if not set(int(x) for x in np.unique(priority)) <= set(self.meta.levels):
                raise ValueError(
                    "rebuild introduces new priority levels; rebuild the orchestrator"
                )
            if candidate_sla is not None and (
                candidate_sla.max_rows > self._T - 1
                or candidate_sla.max_edges > self._E
            ):
                raise ValueError(
                    "rebuild exceeds the padded SLA row/edge shape; rebuild "
                    "the orchestrator"
                )
        if candidate_sla is not None:
            dev_l_new = list(self._dev_l)
            dev_u_new = list(self._dev_u)
            dev_l_new[k] = new_pdn.dev_l
            dev_u_new[k] = new_pdn.dev_u
            dcap_eff = np.array([c[0] for c in self._node_cap]) * self._domain_supply
            dcap_eff[k] = new_pdn.node_cap[0] * self._domain_supply[k]
            self._check_effective_floors(
                dev_l=dev_l_new,
                dev_u=dev_u_new,
                dcap_eff=dcap_eff,
                sla=candidate_sla,
            )
        self._local_pdn[k] = new_pdn
        self._priority[k] = priority.copy()
        self._dev_l[k] = new_pdn.dev_l.copy()
        self._dev_u[k] = new_pdn.dev_u.copy()
        self._node_cap[k] = new_pdn.node_cap.copy()
        self._sla = candidate_sla
        if self.mode == "loop":
            assert self._engines is not None
            self._engines[k] = self._build_engine(k, new_pdn)
            self._invalidate_incremental(k)
        else:
            self._upload()
            self._reset_domain_warm(k)

    def reset_warm(self) -> None:
        self._warm = None
        self._inc_carry = None
        self._loop_prev = None
        if self._engines is not None:
            for e in self._engines:
                e.reset_warm()

    # -- the control step --------------------------------------------------

    def _effective_domain_caps(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(domain_cap, coord_cap, domain_min) under current supply state."""
        dcap = np.array([c[0] for c in self._node_cap]) * self._domain_supply
        ccap = self.coordinator.cap * self._feed_scale
        dmin = np.array([l.sum() for l in self._dev_l])
        return dcap, ccap, dmin

    def _plan(self, demand: np.ndarray, shaped: np.ndarray | None = None):
        """(grants, per-domain SLA row bounds | None, slice_lo, slice_hi)."""
        dcap, ccap, dmin = self._effective_domain_caps()
        if self._sla is None:
            grants = self.coordinator.plan(
                demand,
                domain_cap=dcap,
                coord_cap=ccap,
                domain_min=dmin,
                domain_n=self.domain_sizes,
            )
            return grants, None, None, None
        sf, su, sd = self._slice_aggregates(self._dev_l, self._dev_u, shaped)
        grants, slo, shi = self.coordinator.plan_sla(
            demand,
            sla=self._sla,
            slice_floor=sf,
            slice_umax=su,
            slice_demand=sd if shaped is not None else sf,
            local_lift=self._local_lift(self._dev_l, self._dev_u),
            domain_cap=dcap,
            coord_cap=ccap,
            domain_min=dmin,
            domain_n=self.domain_sizes,
        )
        return grants, self._sla_row_bounds(slo, shi), slo, shi

    def plan(self, demand: np.ndarray) -> np.ndarray:
        """Coordinator grants for a demand vector under current supply
        (with tenants: entitlement rows enforced, demand-free slice split)."""
        return self._plan(demand)[0]

    def step(
        self,
        telemetry: np.ndarray,
        *,
        active: np.ndarray | None = None,
    ) -> FleetStepResult:
        """One fleet control step: telemetry [n] watts -> allocation [n].

        Telemetry, the returned allocation and the Phase I and Phase II
        caps (``phase1``/``phase2``) are in global device order (domain
        concatenation), in every dispatch mode.  Host-side work is O(n) request shaping,
        the O(K + m_above_cut) coordinator plan, and the scatter/gather
        into the per-domain layout; all solves are compiled programs.
        """
        n = self.n
        req = np.asarray(telemetry, np.float64)
        if req.shape != (n,):
            raise ValueError(f"telemetry shape {req.shape} != ({n},)")
        if active is None:
            active = req >= self.idle_threshold
        active = np.asarray(active, bool)
        if active.shape != (n,):
            raise ValueError(f"active shape {active.shape} != ({n},)")
        offs = self._offsets()
        if self.mode == "sharded":
            # demand aggregation + coordinator plan live INSIDE the sharded
            # program (the one cross-shard reduction); the host only shapes
            # the [K, N] scatter and the demand-free planning arrays
            t0 = time.perf_counter()
            with spans.span("fleet.dispatch"):
                res, grants, demand, slice_lo, slice_hi = self._step_sharded(
                    req, active, offs
                )
            wall = time.perf_counter() - t0
        else:
            with spans.span("fleet.shape"):
                l_all = self.device_bounds()
                u_all = self.device_caps()
                shaped = np.where(active, np.clip(req, l_all, u_all), l_all)
                demand = np.array(
                    [shaped[offs[k] : offs[k + 1]].sum() for k in range(self.k)]
                )
            with spans.span("fleet.plan"):
                grants, row_bounds, slice_lo, slice_hi = self._plan(demand, shaped)
            t0 = time.perf_counter()
            with spans.span("fleet.dispatch"):
                if self.mode == "stacked":
                    res = self._step_stacked(req, active, grants, offs, row_bounds)
                else:
                    res = self._step_loop(
                        req, active, grants, offs, row_bounds, demand
                    )
            wall = time.perf_counter() - t0
        alloc, phase1, phase2, stats = res
        if slice_lo is not None:
            stats["slice_lo"] = slice_lo
            stats["slice_hi"] = slice_hi
        out = FleetStepResult(
            allocation=alloc,
            phase1=phase1,
            phase2=phase2,
            grants=grants,
            demand=demand,
            wall_time_s=wall,
            stats=stats,
        )
        iters = np.asarray(stats["iterations"])
        entry = {
            "wall_s": wall,
            "converged": bool(np.all(stats["converged"])),
            "solves": int(np.sum(stats["solves"])),
            "iterations": int(iters.sum()),
            # the domains step in lockstep: the slowest sets the step
            "iterations_max": int(iters.max()),
            "iterations_min": int(iters.min()),
            "granted_W": float(grants.sum()),
            "demand_W": float(demand.sum()),
            "skipped": int(np.sum(stats.get("skipped", False))),
        }
        if "coordinator_rounds" in stats:
            entry["coordinator_rounds"] = int(stats["coordinator_rounds"])
        self.history.append(entry)
        return out

    @property
    def recorder_config(self) -> obs_recorder.RecorderConfig | None:
        return self._rec_cfg

    def flush_recorder(self, *, reset: bool = False) -> dict[str, Any] | None:
        """Gather the flight record to host: ``{"mode", "lanes", ...}`` with
        one per-domain flush dict per lane (see
        :func:`repro.obs.recorder.flush`), or ``None`` when recording is off.

        Stacked/sharded modes flush the orchestrator's own [K, ...] batched
        recorder state; loop mode delegates to each domain engine's
        recorder.  ``reset=True`` clears the buffers after the gather.
        """
        if self._rec_cfg is None:
            return None
        if self.mode in ("stacked", "sharded"):
            if self._rec_state is None:
                lanes: list[dict[str, Any]] = []
            else:
                lanes = obs_recorder.flush_lanes(self._rec_state, self._rec_cfg)
            if reset:
                self._rec_state = None
        else:
            lanes = []
            for eng in self._engines or []:
                f = eng.flush_recorder(reset=reset)
                lanes.append(f["step"] if f is not None and "step" in f else {})
        return {"mode": self.mode, "lanes": lanes}

    def _scatter(self, req, active, offs):
        """Global ``[n]`` telemetry and activity into the padded ``[K, N]``
        per-domain layout."""
        r = np.zeros((self.k, self._N))
        act = np.zeros((self.k, self._N), bool)
        for k in range(self.k):
            nk = int(self.domain_sizes[k])
            r[k, :nk] = req[offs[k] : offs[k + 1]]
            act[k, :nk] = active[offs[k] : offs[k + 1]]
        return r, act

    def _step_stacked(self, req, active, grants, offs, row_bounds=None):
        K, N = self.k, self._N
        r, act = self._scatter(req, active, offs)
        cap = self._cap_np.copy()
        cap[:, 0] = grants
        # per-step SLA rows: real rows get contract/sub-budget bounds, pad
        # rows stay [0, inf) (inert)
        sla_lo = np.zeros((K, self._T))
        sla_hi = np.full((K, self._T), np.inf)
        if row_bounds is not None:
            for k, (lo_k, hi_k) in enumerate(row_bounds):
                sla_lo[k, : lo_k.shape[0]] = lo_k
                sla_hi[k, : hi_k.shape[0]] = hi_k
        inc = self._inc_carry if self.options.incremental else None
        with self._ctx():
            if self._rec_cfg is not None and self._rec_state is None:
                self._rec_state = obs_recorder.init_batch(
                    self._rec_cfg, K, N, self.dtype
                )
            x1, x2, x3, warm_c, stats, new_inc, new_rec = _fleet_step_jit(
                self._dom,
                jnp.asarray(cap, self.dtype),
                jnp.asarray(sla_lo, self.dtype),
                jnp.asarray(sla_hi, self.dtype),
                jnp.asarray(r, self.dtype),
                jnp.asarray(act),
                self._warm,
                inc,
                self._rec_state,
                meta=self.meta,
                opts=self.options.solver,
                rec_cfg=self._rec_cfg,
            )
            x3.block_until_ready()
        if new_rec is not None:
            self._rec_state = new_rec
        self._warm = warm_c
        if self.options.incremental:
            # update_carry(None, ...) seeds a fresh anchor on the first
            # step, so new_inc is a [K, ...]-leaf carry on every path
            self._inc_carry = new_inc
        return (
            *self._gather(x3, x1, x2),
            StepStats.from_jit(stats, mode="stacked"),
        )

    def _gather(self, *xs) -> tuple[np.ndarray, ...]:
        """Each padded ``[K, N]`` device array to host ``[n]`` in global
        device order (the domain concatenation)."""
        sizes = [int(nk) for nk in self.domain_sizes]
        return tuple(
            np.concatenate([row[:nk] for row, nk in zip(x, sizes)])
            for x in jax.device_get(xs)
        )

    def _sharded_plan(self):
        """(PlanRep, RowMaps | None): demand-independent planning arrays for
        the sharded program, from the same host mirrors (and with the same
        per-step validation) as the stacked planner."""
        from repro.fleet import sharded as shd

        dcap, ccap, dmin = self._effective_domain_caps()
        dt = self.dtype
        sla = self._sla
        S = sla.n_slices if sla is not None else 0
        rowmap = None
        slice_lo = np.zeros(0)
        slice_umax = np.zeros(0)
        ten_start = np.zeros(0, np.int32)
        ten_end = np.zeros(0, np.int32)
        b_max_c = np.zeros(0)
        if sla is not None:
            sf, su, _ = self._slice_aggregates(self._dev_l, self._dev_u)
            lift = self._local_lift(self._dev_l, self._dev_u)
            if S:
                check_tenants_deliverable(sla, sf, su)
                slice_lo, _ = split_entitlements(sla, sf, su, sf)
                slice_umax = su
                ten_start, ten_end = sla.ten_start, sla.ten_end
                b_max_c = sla.b_max[sla.cross_ids]
                np.add.at(lift, sla.slice_domain, slice_lo - sf)
            dmin = dmin + lift
            # [K, T] row routing: slice rows gather the coordinator split,
            # local rows carry their contract, pad rows stay [0, inf)
            K, T = self.k, self._T
            idx = np.full((K, T), S, np.int32)
            lo_local = np.zeros((K, T))
            hi_local = np.full((K, T), np.inf)
            for k in range(K):
                for r, t in enumerate(sla.rows[k]):
                    s = int(sla.row_slice[k][r])
                    if s >= 0:
                        idx[k, r] = s
                    else:
                        lo_local[k, r] = sla.b_min[t]
                        hi_local[k, r] = sla.b_max[t]
            rowmap = shd.RowMaps(
                slice_idx=jnp.asarray(idx),
                lo_local=jnp.asarray(lo_local, dt),
                hi_local=jnp.asarray(hi_local, dt),
            )
        # same fail-fast as the host coordinator's _grants
        bad = np.nonzero(dmin > dcap + 1e-9)[0]
        if bad.size:
            k = int(bad[0])
            raise ValueError(
                f"domain {k} minimum draw {dmin[k]:.1f} W exceeds its "
                f"(possibly derated) capacity {dcap[k]:.1f} W; mask devices "
                "out first (FleetLifecycle.device_leave)"
            )
        check_caps_fund_minimums(
            self.coordinator.start,
            self.coordinator.end,
            ccap,
            dmin,
            what="coordinator row",
        )
        rep = shd.PlanRep(
            dmin_tot=jnp.asarray(dmin, dt),
            dcap=jnp.asarray(dcap, dt),
            ccap=jnp.asarray(ccap, dt),
            coord_start=jnp.asarray(self.coordinator.start),
            coord_end=jnp.asarray(self.coordinator.end),
            slice_lo=jnp.asarray(slice_lo, dt),
            slice_umax=jnp.asarray(slice_umax, dt),
            ten_start=jnp.asarray(ten_start),
            ten_end=jnp.asarray(ten_end),
            b_max_c=jnp.asarray(b_max_c, dt),
        )
        return rep, rowmap

    def _step_sharded(self, req, active, offs):
        """The sharded step under ``fleet.dispatch``, its host stages as
        spans: ``fleet.scatter`` (telemetry into the padded ``[K, N]``
        layout), ``fleet.plan`` (the demand-free planning arrays),
        ``fleet.upload`` (per-step inputs to the devices), the program's
        call, ``fleet.wait`` (until the caps are computed), ``fleet.fetch``
        (the three phases' caps to host, in global device order) and
        ``fleet.stats``."""
        from repro.fleet import sharded as shd

        K, N = self.k, self._N
        with spans.span("fleet.scatter"):
            r, act = self._scatter(req, active, offs)
        inc = self._inc_carry if self.options.incremental else None
        with self._ctx():
            if self._rec_cfg is not None and self._rec_state is None:
                self._rec_state = obs_recorder.init_batch(
                    self._rec_cfg, K, N, self.dtype
                )
            with spans.span("fleet.plan"):
                rep, rowmap = self._sharded_plan()
            with spans.span("fleet.upload"):
                cap = jnp.asarray(self._cap_np, self.dtype)
                r_dev = jnp.asarray(r, self.dtype)
                act_dev = jnp.asarray(act)
            out = shd.step(
                self._dom,
                cap,
                r_dev,
                act_dev,
                rowmap,
                self._warm,
                inc,
                rep,
                self._rec_state,
                mesh=self._mesh,
                meta=self.meta,
                opts=self.options.solver,
                coord_mode=self.coordinator.mode,
                rec_cfg=self._rec_cfg,
            )
            with spans.span("fleet.wait"):
                out.x3.block_until_ready()
        self._warm = out.warm
        if self.options.incremental:
            self._inc_carry = out.carry
        if out.rec is not None:
            self._rec_state = out.rec
        with spans.span("fleet.fetch"):
            alloc, phase1, phase2 = self._gather(out.x3, out.x1, out.x2)
            grants, demand, slo, shi = jax.device_get(
                (out.grants, out.demand, out.slice_lo, out.slice_hi)
            )
        with spans.span("fleet.stats"):
            stats = StepStats.from_jit(
                out.stats,
                mode="sharded",
                coordinator_rounds=int(out.coordinator_rounds),
            )
        has_slices = self._sla is not None and self._sla.n_slices > 0
        return (
            (alloc, phase1, phase2, stats),
            grants,
            demand,
            slo if has_slices else None,
            shi if has_slices else None,
        )

    def _loop_domain_clean(self, k, prev, rk, ak, grant_k, rb_k, tol) -> bool:
        """Host-level dirtiness of one loop-mode domain: clean only when the
        per-device telemetry, activity mask, budget grant and SLA row bounds
        are all within ``tol`` of the anchor step whose frozen allocation we
        would serve.  Comparisons are against the *anchor* (not last step),
        so tol-sized drift cannot creep across a chain of skips."""
        if prev["alloc"][k] is None:
            return False
        if abs(float(grant_k) - float(prev["grants"][k])) > tol:
            return False
        if not np.array_equal(ak, prev["active"][k]):
            return False
        if float(np.max(np.abs(rk - prev["req"][k]), initial=0.0)) > tol:
            return False
        prev_rb = prev["row_bounds"][k]
        if (rb_k is None) != (prev_rb is None):
            return False
        if rb_k is not None and not (
            np.allclose(rb_k[0], prev_rb[0], rtol=0.0, atol=tol)
            and np.allclose(rb_k[1], prev_rb[1], rtol=0.0, atol=tol, equal_nan=False)
        ):
            return False
        return True

    def _step_loop(self, req, active, grants, offs, row_bounds=None, demand=None):
        assert self._engines is not None
        inc = self.options.incremental
        tol = self.options.certify_tol
        if inc and self._loop_prev is None:
            K = self.k
            self._loop_prev = {
                "alloc": [None] * K,
                "phase1": [None] * K,
                "phase2": [None] * K,
                "req": [None] * K,
                "active": [None] * K,
                "demand": np.full(K, np.nan),
                "grants": np.full(K, np.nan),
                "row_bounds": [None] * K,
            }
        prev = self._loop_prev
        dirty = (
            self.coordinator.domain_dirtiness(
                demand,
                grants,
                prev["demand"],
                prev["grants"],
                tol=tol,
            )
            if inc and demand is not None
            else np.ones(self.k, bool)
        )
        allocs, phase1, phase2 = [], [], []
        solves, iters, phase_iters, conv = [], [], [], []
        skipped, certify, wf_rounds, wf_levels = [], [], [], []
        pj_steps, pj_levels = [], []
        certified, truncated, kkt_res, restarts, kkt_hist = [], [], [], [], []
        for k, eng in enumerate(self._engines):
            rk = req[offs[k] : offs[k + 1]]
            ak = active[offs[k] : offs[k + 1]]
            rb_k = (
                row_bounds[k]
                if row_bounds is not None and row_bounds[k][0].shape[0]
                else None
            )
            if (
                inc
                and not dirty[k]
                and self._loop_domain_clean(k, prev, rk, ak, grants[k], rb_k, tol)
            ):
                # clean domain: serve the frozen allocation, skip the engine
                # dispatch entirely (the anchor values stay frozen too)
                allocs.append(prev["alloc"][k])
                phase1.append(prev["phase1"][k])
                phase2.append(prev["phase2"][k])
                solves.append(0)
                iters.append(0)
                phase_iters.append([0, 0, 0])
                wf_rounds.append([0, 0])
                wf_levels.append([0, 0])
                pj_steps.append(0)
                pj_levels.append(0)
                conv.append(True)
                skipped.append(True)
                certify.append(True)
                certified.append(True)
                truncated.append(False)
                kkt_res.append(0.0)
                restarts.append(0)
                kkt_hist.append(np.zeros(solver.KKT_HIST_BUCKETS, np.int32))
                continue
            eng.set_root_cap(grants[k])  # traced cap swap: no recompile
            if rb_k is not None:
                # traced SLA-bound swap: tenant sub-budgets, no recompile
                eng.set_sla_bounds(rb_k[0], rb_k[1])
            res = eng.step(rk, active=ak)
            allocs.append(res.allocation)
            phase1.append(res.phase1)
            phase2.append(res.phase2)
            solves.append(res.stats["total_solves"])
            iters.append(res.stats["total_iterations"])
            phase_iters.append(res.stats["phase_iterations"])
            wf_rounds.append(res.stats["waterfill_rounds"])
            wf_levels.append(res.stats["waterfill_levels"])
            pj_steps.append(res.stats["project_steps_p1"])
            pj_levels.append(res.stats["project_levels_p1"])
            conv.append(res.stats["converged"])
            skipped.append(bool(res.stats.get("skipped", False)))
            certify.append(bool(res.stats.get("certify_pass", False)))
            certified.append(bool(res.stats.get("kkt_certified", False)))
            truncated.append(bool(res.stats.get("truncated", False)))
            kkt_res.append(float(res.stats.get("kkt_res", 0.0)))
            restarts.append(int(res.stats.get("restarts", 0)))
            kkt_hist.append(
                np.asarray(
                    res.stats.get(
                        "kkt_hist", np.zeros(solver.KKT_HIST_BUCKETS, np.int32)
                    )
                )
            )
            if inc:
                prev["alloc"][k] = res.allocation
                prev["phase1"][k] = res.phase1
                prev["phase2"][k] = res.phase2
                prev["req"][k] = rk.copy()
                prev["active"][k] = ak.copy()
                if demand is not None:
                    prev["demand"][k] = float(demand[k])
                prev["grants"][k] = float(grants[k])
                prev["row_bounds"][k] = (
                    (rb_k[0].copy(), rb_k[1].copy()) if rb_k is not None else None
                )
        stats = StepStats.build(
            solves=np.asarray(solves),
            iterations=np.asarray(iters),
            phase_iterations=np.asarray(phase_iters),
            converged=np.asarray(conv),
            skipped=np.asarray(skipped),
            certify_pass=np.asarray(certify),
            kkt_certified=np.asarray(certified),
            truncated=np.asarray(truncated),
            kkt_res=np.asarray(kkt_res),
            restarts=np.asarray(restarts),
            kkt_hist=np.stack(kkt_hist, axis=0),
            waterfill_rounds=np.asarray(wf_rounds),
            waterfill_levels=np.asarray(wf_levels),
            project_steps_p1=np.asarray(pj_steps),
            project_levels_p1=np.asarray(pj_levels),
            mode="loop",
        )
        return (
            np.concatenate(allocs),
            np.concatenate(phase1),
            np.concatenate(phase2),
            stats,
        )
