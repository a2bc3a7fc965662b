"""Sharded fleet dispatch: the stacked K-domain control step over a device
mesh.

The stacked dispatch in :mod:`repro.fleet.orchestrator` solves all K
domains as one vmapped program on a single device.  This module shards that
program with ``shard_map`` over a 1-D ``("domains",)`` mesh: every padded
``[K, N]``/``[K, M]``/``[K, E]``/``[K, T]`` array is sharded on its leading
(domain) axis, each shard runs the identical vmapped per-domain three-phase
solve (:func:`repro.fleet.orchestrator._solve_domains` — literally the same
traced body as stacked dispatch), and the **only** cross-shard communication
per control step is the coordinator exchange:

1. each shard reduces its local telemetry to per-domain aggregate demand
   (and, with tenants, per-slice demand sums for cross-cut tenants);
2. ONE ``psum`` over the mesh assembles the global ``[K]`` demand vector
   and ``[S]`` slice-demand vector on every shard;
3. every shard replicates the :class:`BudgetCoordinator` plan — the
   demand + headroom water-filling passes over the above-cut coordinator
   tree (:func:`repro.core.waterfill.waterfill_jax`, the trace-safe twin of
   the host coordinator's numpy sweep) plus the demand-shaped half of the
   tenant entitlement split — and slices out its own domains' budget feeds
   (the "broadcast" leg: grants are computed replicated, consumed locally).

Everything demand-*independent* — effective domain floors incl. tenant
minimum lifts, derated caps, the demand-free entitlement minimums — is
prepared on the host from the orchestrator's mirrors exactly as the stacked
planner does, and enters the program as small replicated *traced* arrays.
Supply derates, grant changes, device join/leave re-pins and
``set_tenant_bounds`` therefore recompile nothing (see
:func:`trace_count`); only a structural rebuild that changes the padded
shapes or the cross-cut slice structure retraces.

Shard count: the largest divisor of K that is <= the local device count
(`XLA_FLAGS=--xla_force_host_platform_device_count=8` forces a multi-device
CPU mesh); a 1-device mesh degenerates to the stacked program plus trivial
collectives, which keeps every test runnable on a bare CPU.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.treeops import TreeTopo
from repro.core.waterfill import waterfill_jax

__all__ = [
    "PlanRep",
    "RowMaps",
    "StepOut",
    "build_mesh",
    "shard_count",
    "step",
    "trace_count",
]

_AXIS = "domains"

# sharded-dispatch retrace counter (the sharded twin of
# repro.fleet.orchestrator.trace_count)
_N_TRACES = 0


def trace_count() -> int:
    """Times the sharded fleet program has been traced in this process."""
    return _N_TRACES


def shard_count(k: int, n_devices: int | None = None) -> int:
    """Largest divisor of ``k`` that fits the local device count (domains
    are never split across shards, so the mesh size must divide K)."""
    if n_devices is None:
        n_devices = len(jax.devices())
    d = max(1, min(int(n_devices), int(k)))
    while k % d:
        d -= 1
    return d


def build_mesh(k: int) -> Mesh:
    """A 1-D ``("domains",)`` mesh over ``shard_count(k)`` local devices."""
    d = shard_count(k)
    return Mesh(np.array(jax.devices()[:d]), (_AXIS,))


def domain_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis (domain) sharding for the padded ``[K, ...]`` arrays."""
    return NamedSharding(mesh, P(_AXIS))


class RowMaps(NamedTuple):
    """[K, T] per-SLA-row routing, sharded on K.  ``slice_idx`` points into
    the global slice arrays (``S`` = an always-inert extra slot for
    domain-local and pad rows); ``lo_local``/``hi_local`` carry the
    contractual bounds of domain-local rows ([0, inf) elsewhere, so
    ``max``/``min`` against the slice gather needs no mask)."""

    slice_idx: jnp.ndarray  # [K, T] int32 in [0, S]
    lo_local: jnp.ndarray  # [K, T]
    hi_local: jnp.ndarray  # [K, T]


class PlanRep(NamedTuple):
    """Replicated traced planning state (all demand-independent; rebuilt on
    the host every step from the orchestrator mirrors, exactly like the
    stacked planner's inputs — so mutations stay zero-recompile)."""

    dmin_tot: jnp.ndarray  # [K] domain floors + tenant minimum lifts
    dcap: jnp.ndarray  # [K] derated domain caps
    ccap: jnp.ndarray  # [m_anc] derated coordinator-row caps
    coord_start: jnp.ndarray  # [m_anc] int32 (domain-index ranges)
    coord_end: jnp.ndarray  # [m_anc] int32
    slice_lo: jnp.ndarray  # [S] demand-free entitlement minimum split
    slice_umax: jnp.ndarray  # [S] per-slice deliverable maximum
    ten_start: jnp.ndarray  # [Tc] int32 slice ranges per cross-cut tenant
    ten_end: jnp.ndarray  # [Tc] int32
    b_max_c: jnp.ndarray  # [Tc] cross-cut tenant contractual maxima


class StepOut(NamedTuple):
    """One sharded step's outputs: ``[K, ...]`` leaves sharded on the domain
    axis, the coordinator's outputs replicated."""

    x1: jnp.ndarray  # [K, N] Phase I caps
    x2: jnp.ndarray  # [K, N] Phase II caps
    x3: jnp.ndarray  # [K, N] final caps
    warm: object  # phases.WarmCarry, [K, ...] leaves
    stats: dict  # per-domain solver stats, [K] leaves
    carry: object  # incremental anchor ([K, ...] leaves) or None
    grants: jnp.ndarray  # [K] coordinator budget grants
    demand: jnp.ndarray  # [K] per-domain shaped demand
    slice_lo: jnp.ndarray  # [S] tenant slice floors
    slice_hi: jnp.ndarray  # [S] tenant slice sub-budgets
    rec: object  # flight-recorder state ([K, ...] leaves) or None
    coordinator_rounds: jnp.ndarray  # int32: rounds of the two grant passes


def _sharded_solve(
    dom, cap, r, active, rowmap, warm, carry, rep, rec,
    *, meta, opts, coord_mode, k_total, rec_cfg,
):
    """Per-shard body: local aggregates -> one psum -> replicated
    coordinator plan -> local feeds -> the vmapped per-domain solve."""
    global _N_TRACES
    _N_TRACES += 1  # executes at trace time only

    from repro.fleet.orchestrator import _solve_domains

    dt = dom.l.dtype
    k_loc = dom.l.shape[0]
    idx = lax.axis_index(_AXIS)
    S = rep.slice_lo.shape[0]

    with jax.named_scope("coordinator"):
        shaped = jnp.where(active, jnp.clip(r, dom.l, dom.u), dom.l)
        demand_loc = jnp.sum(shaped, axis=1)

        # -- the one cross-shard reduction: [K] demand (+ [S] slice demand)
        agg = jnp.zeros((k_total + S,), dt)
        agg = lax.dynamic_update_slice(agg, demand_loc, (idx * k_loc,))
        if S:
            T = rowmap.lo_local.shape[1]

            def rowsum(sh, dev, ten):
                return jax.ops.segment_sum(sh[dev], ten, num_segments=T)

            row_demand = jax.vmap(rowsum)(shaped, dom.sla_dev, dom.sla_ten)
            part = jnp.zeros((S + 1,), dt)
            part = part.at[rowmap.slice_idx.reshape(-1)].add(row_demand.reshape(-1))
            agg = agg.at[k_total:].add(part[:S])
        agg = lax.psum(agg, _AXIS)
        demand = agg[:k_total]

        # -- replicated coordinator plan (waterfill over the above-cut tree)
        ctree = TreeTopo(
            start=rep.coord_start,
            end=rep.coord_end,
            cap=rep.ccap,
            depth=jnp.zeros(rep.ccap.shape[0], jnp.int32),
        )
        mask_k = jnp.ones((k_total,), bool)
        grants = rep.dmin_tot
        rounds = jnp.zeros((), jnp.int32)
        if coord_mode == "waterfill":
            grants, rounds = waterfill_jax(
                grants, mask_k, ctree, jnp.clip(demand, rep.dmin_tot, rep.dcap)
            )
        grants, headroom_rounds = waterfill_jax(grants, mask_k, ctree, rep.dcap)
        rounds = rounds + headroom_rounds

        if S:
            slice_demand = agg[k_total:]
            forest = TreeTopo(
                start=rep.ten_start,
                end=rep.ten_end,
                cap=rep.b_max_c,
                depth=jnp.zeros(rep.b_max_c.shape[0], jnp.int32),
            )
            mask_s = jnp.ones((S,), bool)
            slice_hi, _ = waterfill_jax(
                rep.slice_lo,
                mask_s,
                forest,
                jnp.clip(slice_demand, rep.slice_lo, rep.slice_umax),
            )
            slice_hi, _ = waterfill_jax(slice_hi, mask_s, forest, rep.slice_umax)
            lo_ext = jnp.concatenate([rep.slice_lo, jnp.zeros((1,), dt)])
            hi_ext = jnp.concatenate([slice_hi, jnp.full((1,), jnp.inf, dt)])
            sla_lo = jnp.maximum(rowmap.lo_local, lo_ext[rowmap.slice_idx])
            sla_hi = jnp.minimum(rowmap.hi_local, hi_ext[rowmap.slice_idx])
            slice_hi_out = slice_hi
        elif rowmap is not None:
            sla_lo, sla_hi = rowmap.lo_local, rowmap.hi_local
            slice_hi_out = rep.slice_lo
        else:
            sla_lo = jnp.zeros((k_loc, 0), dt)
            sla_hi = jnp.zeros((k_loc, 0), dt)
            slice_hi_out = rep.slice_lo

        # -- broadcast leg: every shard consumes its own domains' feeds ----
        grants_loc = lax.dynamic_slice_in_dim(grants, idx * k_loc, k_loc)
        cap_step = cap.at[:, 0].set(grants_loc)

    with jax.named_scope("domains"):
        x1, x2, x3, wcarry, stats, new_inc, new_rec = _solve_domains(
            dom, cap_step, sla_lo, sla_hi, r, active, warm, carry, rec,
            meta=meta, opts=opts, rec_cfg=rec_cfg,
        )
    # per-shard incremental dispatch: each shard's all-skip cond branches
    # independently inside _solve_domains (no collectives on either side);
    # recording is shard-local too — each shard appends its own lanes
    return StepOut(
        x1, x2, x3, wcarry, stats, new_inc, grants, demand,
        rep.slice_lo, slice_hi_out, new_rec, rounds,
    )


@functools.partial(
    jax.jit, static_argnames=("mesh", "meta", "opts", "coord_mode", "rec_cfg")
)
def _step_jit(
    dom, cap, r, active, rowmap, warm, carry, rep, rec,
    *, mesh, meta, opts, coord_mode, rec_cfg,
):
    body = functools.partial(
        _sharded_solve,
        meta=meta,
        opts=opts,
        coord_mode=coord_mode,
        k_total=dom.l.shape[0],
        rec_cfg=rec_cfg,
    )
    sharded, rep_spec = P(_AXIS), P()
    # the replication checker is off: the coordinator outputs are replicated
    # by construction (computed from one psum), which it cannot verify
    fn = jax.shard_map(
        body,
        mesh=mesh,
        check_vma=False,
        in_specs=(
            sharded,
            sharded,
            sharded,
            sharded,
            sharded,
            sharded,
            sharded,
            rep_spec,
            sharded,
        ),
        out_specs=StepOut(
            x1=sharded,
            x2=sharded,
            x3=sharded,
            warm=sharded,
            stats=sharded,
            carry=sharded,
            grants=rep_spec,
            demand=rep_spec,
            slice_lo=rep_spec,
            slice_hi=rep_spec,
            rec=sharded,
            coordinator_rounds=rep_spec,
        ),
    )
    return fn(dom, cap, r, active, rowmap, warm, carry, rep, rec)


def step(
    dom, cap, r, active, rowmap, warm, carry, rep, rec=None,
    *, mesh, meta, opts, coord_mode, rec_cfg=None,
):
    """One sharded fleet control step.  All array arguments are traced (the
    zero-recompile contract); ``meta``/``opts``/``coord_mode``/``mesh`` (and
    the flight-recorder ``rec_cfg``) are the only statics.  ``carry`` is the
    incremental certify anchor with domain-sharded ``[K, ...]`` leaves (None
    outside incremental mode); ``rec`` is the domain-sharded
    :class:`repro.obs.recorder.RecorderState` batch (None when recording is
    off).  Returns a :class:`StepOut`.

    The program's named scopes split its device time: ``coordinator``
    (local demand reduction, the ``psum``, the replicated plan and the
    grants' broadcast) and ``domains`` (the vmapped per-domain solves)."""
    if coord_mode not in ("waterfill", "subtree"):
        raise ValueError(
            f"sharded dispatch supports waterfill/subtree coordinators, "
            f"got {coord_mode!r}"
        )
    return _step_jit(
        dom,
        cap,
        r,
        active,
        rowmap,
        warm,
        carry,
        rep,
        rec,
        mesh=mesh,
        meta=meta,
        opts=opts,
        coord_mode=coord_mode,
        rec_cfg=rec_cfg,
    )
