"""Incremental re-solve (PR 7): certify tiers, the 200-step mixed-trace
parity regression, stats uniformity, and fleet dirty-domain dispatch.

The central contract: with ``NvpaxOptions(incremental=True)`` every path
(host ``optimize``, ``optimize_batched``, ``AllocEngine``, the fleet
orchestrator) returns allocations matching an always-full-solve twin to
solver tolerance, records ``stats["skipped"]``/``stats["certify_pass"]``,
and recompiles nothing across skip/solve transitions.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.core import engine as engine_mod
from repro.core.engine import AllocEngine
from repro.core.nvpax import NvpaxOptions, optimize
from repro.core.problem import AllocProblem
from repro.core.solver import SolverOptions
from repro.core.treeops import SlaTopo
from repro.pdn.tree import build_from_level_sizes

# tight tolerance: parity asserts compare two independently warm-started
# solvers, so the baseline's own solution variability must sit below the
# 1e-6 W bar (see benchmarks/incremental_bench.py)
TIGHT = NvpaxOptions(solver=SolverOptions(eps_abs=1e-9, eps_rel=1e-9))
TIGHT_INC = NvpaxOptions(
    incremental=True, solver=SolverOptions(eps_abs=1e-9, eps_rel=1e-9)
)


def small_pdn():
    return build_from_level_sizes([2, 2], gpus_per_server=4, l=200.0, u=700.0)


# -- certify tiers (one lane) ----------------------------------------------


def test_certify_full_skip_on_identical_step():
    pdn = small_pdn()
    rng = np.random.default_rng(0)
    tele = rng.uniform(250, 650, pdn.n)
    ap = AllocProblem.build(pdn, tele)
    res = optimize(ap, TIGHT_INC)
    assert res.carry is not None
    assert not res.stats["skipped"]
    res2 = optimize(ap, TIGHT_INC, warm=res.warm_state, carry=res.carry)
    assert res2.stats["skipped"] and res2.stats["certify_pass"]
    assert res2.stats["total_iterations"] == 0
    np.testing.assert_array_equal(res2.allocation, res.allocation)


@pytest.mark.parametrize("path", ["optimize", "engine"])
def test_full_skip_returns_the_anchor_phases(path):
    """A skipped step returns the anchor solve's Phase I and Phase II caps,
    not its final caps twice: idle devices make Phase II and the final caps
    differ."""
    pdn = small_pdn()
    tele = np.random.default_rng(5).uniform(250, 450, pdn.n)
    tele[pdn.n // 2 :] = 50.0  # an idle rack: Phase III raises it
    if path == "optimize":
        ap = AllocProblem.build(pdn, tele)
        first = optimize(ap, TIGHT_INC)
        again = optimize(ap, TIGHT_INC, warm=first.warm_state, carry=first.carry)
    else:
        eng = AllocEngine(pdn, options=TIGHT_INC)
        first, again = eng.step(tele), eng.step(tele)
    assert again.stats["skipped"] and not first.stats["skipped"]
    assert np.max(first.allocation - first.phase2) > 1.0
    for name in ("phase1", "phase2", "allocation"):
        np.testing.assert_allclose(
            getattr(again, name), getattr(first, name), rtol=0, atol=1e-9
        )


def test_certify_rejects_demand_move():
    # the max-min phases hand out surplus as base-relative increments, so
    # ANY demand move must force a re-solve — even on a device that holds
    # far more than it asks for (the unsound "margin-held" shortcut)
    pdn = small_pdn()
    tele = np.full(pdn.n, 300.0)  # deep surplus everywhere
    ap = AllocProblem.build(pdn, tele)
    res = optimize(ap, TIGHT_INC)
    tele2 = tele.copy()
    tele2[3] += 5.0  # still far below its allocation
    ap2 = AllocProblem.build(pdn, tele2)
    res2 = optimize(ap2, TIGHT_INC, warm=res.warm_state, carry=res.carry)
    assert not res2.stats["skipped"]
    ref = optimize(ap2, TIGHT)
    assert np.abs(res2.allocation - ref.allocation).max() <= 1e-6


def test_certify_phase1_skip_on_slack_cap_move():
    pdn = small_pdn()
    tele = np.full(pdn.n, 300.0)  # light load: huge cap slack
    ap = AllocProblem.build(pdn, tele)
    res = optimize(ap, TIGHT_INC)
    cap2 = np.asarray(pdn.node_cap, np.float64).copy()
    cap2[0] -= 50.0  # slack still >> certify_margin
    pdn2 = dataclasses.replace(pdn, node_cap=cap2)
    ap2 = AllocProblem.build(pdn2, tele)
    res2 = optimize(ap2, TIGHT_INC, warm=res.warm_state, carry=res.carry)
    # caps moved -> no full skip; demands held + slack -> Phase I reused
    assert not res2.stats["skipped"]
    assert res2.stats["certify_pass"]
    assert res2.stats["phase_iterations"][0] == 0
    ref = optimize(ap2, TIGHT)
    assert np.abs(res2.allocation - ref.allocation).max() <= 1e-6


# -- 200-step mixed-trace parity regression --------------------------------


def _drive_mixed_trace(sla: SlaTopo | None):
    """Drive an incremental and an always-full engine over the 200-step
    mixed trace (quasi-static cadence, brownout, optional tenant-contract
    change, churn re-pin).  Returns per-step parities, the always-full
    baseline's self-drift on held steps, and the skip count; asserts the
    zero-retrace contract and (with tenants) the minimums inline."""
    pdn = build_from_level_sizes([2, 4], gpus_per_server=8, l=200.0, u=700.0)
    n = pdn.n  # 64
    full = AllocEngine(pdn, sla=sla, options=TIGHT)
    inc = AllocEngine(pdn, sla=sla, options=TIGHT_INC)
    sla_lo = None if sla is None else np.asarray(sla.lo, np.float64).copy()

    rng = np.random.default_rng(7)
    base = rng.uniform(250, 650, n)
    cap0 = float(pdn.node_cap[0])

    # warmup past cold/steady/skip jit variants of both engines, then the
    # whole 200-step run — including the brownout, contract-change and
    # re-pin events — must trace nothing new
    for _ in range(3):
        full.step(base)
        inc.step(base)
    traces0 = engine_mod.trace_count()

    skips = 0
    parities: list[float] = []
    self_drift = 0.0
    tele = base
    prev_tele = None
    prev_full = None
    for t in range(200):
        if t % 5 == 0:  # quasi-static refresh cadence
            tele = base * rng.uniform(0.97, 1.03, n)
        if t == 80:  # brownout: derate the root budget
            for e in (full, inc):
                e.set_root_cap(0.9 * cap0)
        if t == 120 and sla is not None:  # raise tenant 0's minimum
            sla_lo = sla_lo.copy()
            sla_lo[0] = 3800.0
            for e in (full, inc):
                e.set_sla_bounds(sla_lo, np.asarray(sla.hi, np.float64))
        if t == 160:  # churn re-pin: two devices leave the fleet
            dev_l = np.asarray(pdn.dev_l, np.float64).copy()
            dev_u = np.asarray(pdn.dev_u, np.float64).copy()
            dev_l[40:42] = 0.0
            dev_u[40:42] = 0.0
            for e in (full, inc):
                e.repin(dev_l=dev_l, dev_u=dev_u, reset_warm=True)
        rf = full.step(tele)
        ri = inc.step(tele)
        parities.append(float(np.abs(ri.allocation - rf.allocation).max()))
        if prev_full is not None and prev_tele is tele and t not in (80, 120, 160):
            self_drift = max(
                self_drift, float(np.abs(rf.allocation - prev_full).max())
            )
        prev_full = rf.allocation.copy()
        prev_tele = tele
        if sla_lo is not None:
            for ten in range(2):
                dev = np.asarray(sla.dev)[np.asarray(sla.ten) == ten]
                assert ri.allocation[dev].sum() >= sla_lo[ten] - 1e-6, (t, ten)
        skips += int(ri.stats["skipped"])
        assert not rf.stats["skipped"]
    assert engine_mod.trace_count() == traces0
    return parities, self_drift, skips


def test_mixed_trace_parity_200_steps():
    """SLA-free mixed trace: the max-min phases run the exact waterfill
    fast path, so both engines are deterministic and parity vs the
    always-full twin must hold <= 1e-6 W on every one of the 200 steps."""
    parities, _, skips = _drive_mixed_trace(None)
    assert max(parities) <= 1e-6, max(parities)
    # 4 of every 5 steps hold telemetry; events only cost isolated re-solves
    assert skips >= 120, skips


def test_mixed_trace_tenant_minimums_200_steps():
    """Tenant-SLA mixed trace (adds the contract-change event): minimums
    held on every step and parity bounded by the baseline's own noise
    floor.  With SLA rows the max-min program is solved by PDHG on an
    eps-regularized plateau, so the always-full baseline moves its OWN
    answer between re-solves of identical telemetry; the frozen certify
    anchor cannot agree with the baseline more tightly than the baseline
    agrees with itself (same bar as benchmarks/incremental_bench.py)."""
    # two tenants over the first 32 devices; positive minimums at build time
    # so the engine compiles without the pin-free simplification and the
    # step-120 contract change may raise them further
    sla = SlaTopo(
        dev=np.arange(32, dtype=np.int32),
        ten=np.repeat(np.arange(2, dtype=np.int32), 16),
        lo=np.array([3300.0, 3300.0]),
        hi=np.array([16 * 700.0, 16 * 700.0]),
    )
    parities, self_drift, skips = _drive_mixed_trace(sla)
    bar = max(1e-6, 5 * self_drift)
    assert max(parities) <= bar, (max(parities), bar)
    assert skips >= 120, skips


# -- stats uniformity across paths -----------------------------------------


def test_batched_stats_survive_vmap():
    pdn = small_pdn()
    rng = np.random.default_rng(3)
    tb = rng.uniform(250, 650, (3, pdn.n))
    eng = AllocEngine(pdn, options=TIGHT_INC)
    r1 = eng.step_batched(tb)
    assert r1.stats["skipped"].shape == (3,)
    assert not r1.stats["skipped"].any()
    r2 = eng.step_batched(tb)  # identical batch: every lane certifies
    assert r2.stats["skipped"].all() and r2.stats["certify_pass"].all()
    assert (r2.stats["iterations"] == 0).all()
    assert r2.stats["phase_iterations"].shape == (3, 3)
    # the skip path re-emits the carried vertex through the traced
    # projection, so agreement is float-noise-exact rather than bitwise
    assert np.abs(r2.allocation - r1.allocation).max() <= 1e-9
    # one dirty lane re-solves; clean lanes stay frozen on the masked path
    tb2 = tb.copy()
    tb2[1] *= 1.05
    r3 = eng.step_batched(tb2)
    assert list(r3.stats["skipped"]) == [True, False, True]
    full = AllocEngine(pdn, options=TIGHT)
    ref = full.step_batched(tb2)
    assert np.abs(r3.allocation - ref.allocation).max() <= 1e-6


def test_host_engine_fleet_stats_uniform():
    from repro.fleet.orchestrator import FleetOrchestrator

    pdn = build_from_level_sizes([2, 4], gpus_per_server=8)
    rng = np.random.default_rng(1)
    tele = rng.uniform(250, 650, pdn.n)
    ap = AllocProblem.build(pdn, tele)
    host = optimize(ap, TIGHT_INC).stats
    eng = AllocEngine(pdn, options=TIGHT_INC).step(tele).stats
    orch = FleetOrchestrator(pdn, level=1, mode="stacked", options=TIGHT_INC)
    fleet = orch.step(tele).stats
    for stats in (host, eng, fleet):
        for key in ("phase_iterations", "skipped", "certify_pass"):
            assert key in stats, key
    assert np.asarray(fleet["skipped"]).shape == (orch.k,)
    assert np.asarray(fleet["phase_iterations"]).shape == (orch.k, 3)


# -- fleet dirty-domain dispatch -------------------------------------------


@pytest.mark.parametrize("mode", ["stacked", "loop", "sharded"])
def test_fleet_dirty_domain_dispatch(mode):
    from repro.fleet import sharded as shd
    from repro.fleet.orchestrator import FleetOrchestrator
    from repro.fleet.orchestrator import trace_count as fleet_trace_count

    pdn = build_from_level_sizes([4, 4], gpus_per_server=8)
    rng = np.random.default_rng(5)
    tele = rng.uniform(250, 650, pdn.n)
    full = FleetOrchestrator(pdn, level=1, mode=mode, options=TIGHT)
    inc = FleetOrchestrator(pdn, level=1, mode=mode, options=TIGHT_INC)
    for _ in range(2):
        rf = full.step(tele)
        inc.step(tele)
    count = shd.trace_count if mode == "sharded" else fleet_trace_count
    traces0 = count()
    r3 = inc.step(tele)  # frozen telemetry: every domain certifies
    assert np.asarray(r3.stats["skipped"]).all()
    assert int(np.sum(r3.stats["iterations"])) == 0
    assert np.abs(r3.allocation - rf.allocation).max() <= 1e-6
    # domain 0's devices move but its aggregate demand is preserved (watts
    # shift between two unclipped devices), so the coordinator's grants are
    # unchanged and only domain 0 is dirty.  A demand-*changing* move would
    # rightly dirty every domain: the binding root cap makes the headroom
    # waterfill redistribute every grant.
    tele2 = tele.copy()
    tele2[0] += 30.0
    tele2[1] -= 30.0
    r4 = inc.step(tele2)
    skipped = np.asarray(r4.stats["skipped"])
    assert not skipped[0]
    assert skipped[1:].all()  # clean domains are served frozen
    r4f = full.step(tele2)
    assert np.abs(r4.allocation - r4f.allocation).max() <= 1e-6
    assert count() == traces0  # skip/solve transitions share one program


def test_fleet_repin_invalidates_carry():
    from repro.fleet.orchestrator import FleetOrchestrator

    pdn = build_from_level_sizes([4, 4], gpus_per_server=8)
    rng = np.random.default_rng(9)
    tele = rng.uniform(250, 650, pdn.n)
    for mode in ("stacked", "loop"):
        full = FleetOrchestrator(pdn, level=1, mode=mode, options=TIGHT)
        inc = FleetOrchestrator(pdn, level=1, mode=mode, options=TIGHT_INC)
        for _ in range(2):
            full.step(tele)
            inc.step(tele)
        # shrink domain 1's device caps: its frozen allocation is stale
        nk = int(inc.domain_sizes[1])
        off = int(np.cumsum([0, *inc.domain_sizes])[1])
        new_u = np.full(nk, 500.0)
        for orch in (full, inc):
            orch.repin_domain(1, dev_u=new_u, reset_warm=False)
        rf = full.step(tele)
        ri = inc.step(tele)
        assert not np.asarray(ri.stats["skipped"])[1], mode
        assert np.abs(ri.allocation - rf.allocation).max() <= 1e-6, mode
        assert ri.allocation[off : off + nk].max() <= 500.0 + 1e-9, mode
