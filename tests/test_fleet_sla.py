"""Cross-domain tenant SLA enforcement in the fleet coordinator (ISSUE 4).

Acceptance criteria covered here:

* a fleet with cross-cut tenants matches the monolithic SLA engine to
  <= 1e-6 W total power on the same PDN (stacked and loop dispatch);
* tenant contractual minimums are satisfied every step of a brownout
  where static equal-share violates them;
* tenant-minimum preservation across ``device_leave``/``device_join`` on
  a cross-cut tenant, and zero-recompile re-pins when tenant grants
  change (trace-count assertions, mirroring the PR 3 churn tests).
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core import engine as engine_mod
from repro.core.engine import AllocEngine
from repro.core.nvpax import NvpaxOptions
from repro.core.solver import SolverOptions
from repro.fleet import (
    BudgetCoordinator,
    FleetLifecycle,
    FleetOrchestrator,
    split_entitlements,
    split_pdn,
)
from repro.fleet import orchestrator as orch_mod
from repro.pdn.hierarchy_gen import homogeneous_fleet
from repro.pdn.tenants import TenantLayout, assign_cross_domain_tenants

# Pre-overhaul these ran with a 2k-iteration cap: the max-min LP reached
# its vertex long before PDHG could certify KKT on the eps-degenerate SLA
# programs, and the <=1e-6 parity below held only because BOTH solves
# truncated at the same repair-snapped vertex.  The solver-core overhaul
# (repro.core.solver: adaptive restarts + preconditioning) certifies these
# programs, so the tests now run to certification at tight tolerance —
# binding rows land machine-exact on the vertex and parity holds by
# convergence, not by the truncation artifact.
OPTS = NvpaxOptions(
    solver=SolverOptions(eps_abs=1e-11, eps_rel=1e-11, max_iters=20_000)
)


def _layout(pdn, lo_frac=0.35, hi_frac=0.55):
    """One cross-cut tenant over domains 0/1 + one domain-local tenant."""
    tenant_of = np.full(pdn.n, -1, np.int32)
    tenant_of[[0, 1, 16, 17]] = 0  # two devices in each domain
    tenant_of[[4, 5, 6]] = 1  # local to domain 0
    b_min = np.zeros(2)
    b_max = np.zeros(2)
    for t in range(2):
        umax = pdn.dev_u[tenant_of == t].sum()
        b_min[t], b_max[t] = lo_frac * umax, hi_frac * umax
    return TenantLayout(tenant_of, 2, b_min, b_max, np.ones(pdn.n, np.int32))


@pytest.fixture(scope="module")
def slack_pdn():
    """2 domains x 16 devices; node caps strictly above the subtree maxima
    so only device boxes and tenant rows can bind (the exact-parity
    regime for SLA fleets)."""
    return homogeneous_fleet(2, domain_oversub=1.15, root_oversub=1.0)


@pytest.fixture(scope="module")
def binding_pdn():
    """Same geometry with binding domain caps (0.85 oversub)."""
    return homogeneous_fleet(2, domain_oversub=0.85, root_oversub=1.0)


# ---------------------------------------------------------------------------
# partition: classification + layout structure
# ---------------------------------------------------------------------------


def test_partition_classifies_tenants(slack_pdn):
    lay = _layout(slack_pdn)
    part = split_pdn(slack_pdn, 1, tenants=lay)
    sla = part.sla
    assert sla.cross.tolist() == [True, False]
    assert sla.n_slices == 2
    np.testing.assert_array_equal(sla.slice_tenant, [0, 0])
    np.testing.assert_array_equal(sla.slice_domain, [0, 1])
    # domain 0 holds the cross slice AND the local tenant; domain 1 only
    # the cross slice
    assert sla.rows[0].tolist() == [0, 1]
    assert sla.rows[1].tolist() == [0]
    assert sla.row_slice[0].tolist() == [0, -1]
    assert sla.row_slice[1].tolist() == [1]
    # incidence edges are local device indices
    dev0, ten0 = sla.edges(0)
    assert dev0.tolist() == [0, 1, 4, 5, 6]
    assert ten0.tolist() == [0, 0, 1, 1, 1]
    dev1, ten1 = sla.edges(1)
    assert dev1.tolist() == [0, 1]  # global 16, 17 rebased
    assert ten1.tolist() == [0, 0]


def test_entitlement_split_invariants(slack_pdn):
    lay = _layout(slack_pdn)
    sla = split_pdn(slack_pdn, 1, tenants=lay).sla
    floor = np.array([400.0, 400.0])
    umax = np.array([1400.0, 1400.0])
    demand = np.array([1300.0, 500.0])
    lo, hi = split_entitlements(sla, floor, umax, demand)
    assert (lo >= floor - 1e-9).all() and (hi <= umax + 1e-9).all()
    assert (lo <= hi + 1e-9).all()
    # minimum split sums to b_min; maximum split sums to b_max and is
    # steered toward the hot slice
    assert abs(lo.sum() - lay.b_min[0]) < 1e-6
    assert abs(hi.sum() - lay.b_max[0]) < 1e-6
    assert hi[0] > hi[1]


def test_coordinator_plan_sla_funds_minimums(binding_pdn):
    lay = _layout(binding_pdn, lo_frac=0.6, hi_frac=0.8)
    part = split_pdn(binding_pdn, 1, tenants=lay)
    coord = BudgetCoordinator(part)
    sla = part.sla
    floor = np.array([400.0, 400.0])
    umax = np.array([1400.0, 1400.0])
    local_lift = np.array([max(lay.b_min[1] - 600.0, 0.0), 0.0])
    grants, lo, hi = coord.plan_sla(
        np.full(part.k, 1000.0),
        sla=sla,
        slice_floor=floor,
        slice_umax=umax,
        slice_demand=floor,
        local_lift=local_lift,
    )
    coord.check(grants)
    # every grant funds its domain's device floors + tenant minimum lifts
    lifts = np.zeros(part.k)
    np.add.at(lifts, sla.slice_domain, lo - floor)
    lifts += local_lift
    assert (grants >= coord.domain_min + lifts - 1e-9).all()
    # an undeliverable minimum (slices cannot reach b_min) raises
    with pytest.raises(ValueError, match="deliverable maximum"):
        coord.plan_sla(
            np.full(part.k, 1000.0),
            sla=sla,
            slice_floor=floor,
            slice_umax=np.array([700.0, 700.0]),  # sum 1400 < b_min 1680
            slice_demand=floor,
        )


# ---------------------------------------------------------------------------
# parity vs the monolithic SLA engine (acceptance: <= 1e-6 W total)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["stacked", "loop"])
def test_fleet_sla_parity_vs_monolithic(slack_pdn, mode):
    lay = _layout(slack_pdn)
    mono = AllocEngine(
        slack_pdn, sla=lay.sla_topo(), priority=lay.priority, options=OPTS
    )
    orch = FleetOrchestrator(
        slack_pdn,
        level=1,
        coordinator_mode="subtree",
        tenants=lay,
        mode=mode,
        options=OPTS,
    )
    rng = np.random.default_rng(0)
    t_of = lay.tenant_of
    for t in range(3):  # cold + two warm-carried steps
        tele = rng.uniform(600, 690, slack_pdn.n)
        rm = mono.step(tele)
        rf = orch.step(tele)
        assert abs(rm.allocation.sum() - rf.allocation.sum()) <= 1e-6
        for tt in range(lay.n_tenants):
            s = rf.allocation[t_of == tt].sum()
            assert lay.b_min[tt] - 1e-4 <= s <= lay.b_max[tt] + 1e-4
            # the contractual maximum binds under this load in BOTH solves
            assert abs(s - rm.allocation[t_of == tt].sum()) <= 1e-6


def test_fleet_sla_generated_layout_feasible(binding_pdn):
    """The cross-tenant generator + waterfill coordinator end to end."""
    lay = assign_cross_domain_tenants(binding_pdn, 1, seed=3)
    orch = FleetOrchestrator(binding_pdn, level=1, tenants=lay, options=OPTS)
    tele = np.random.default_rng(4).uniform(300, 690, binding_pdn.n)
    res = orch.step(tele)
    for t in range(lay.n_tenants):
        s = res.allocation[lay.tenant_of == t].sum()
        assert lay.b_min[t] - 1e-4 <= s <= lay.b_max[t] + 1e-4
    # globally feasible
    csum = np.concatenate([[0.0], np.cumsum(res.allocation)])
    sums = csum[binding_pdn.node_end] - csum[binding_pdn.node_start]
    assert (sums <= binding_pdn.node_cap + 1e-6).all()


# ---------------------------------------------------------------------------
# brownout: minimums honored where static equal-share violates them
# ---------------------------------------------------------------------------


def test_brownout_honors_tenant_minimums(binding_pdn):
    pdn = binding_pdn
    t_of = np.full(pdn.n, -1, np.int32)
    t_of[[0, 1, 16, 17]] = 0  # cross-cut tenant over both domains
    umax = pdn.dev_u[t_of == 0].sum()
    lay = TenantLayout(
        t_of,
        1,
        np.array([0.7 * umax]),
        np.array([0.9 * umax]),
        np.ones(pdn.n, np.int32),
    )
    orch = FleetOrchestrator(pdn, level=1, tenants=lay, options=OPTS)
    orch.set_domain_supply(0, 0.5)  # domain 0 feed derates
    rng = np.random.default_rng(2)
    for _ in range(3):
        tele = rng.uniform(600, 690, pdn.n)
        res = orch.step(tele)
        s = res.allocation[t_of == 0].sum()
        assert s >= 0.7 * umax - 1e-4  # contractual minimum honored
    # static equal share (locally derated to stay feasible) violates it
    a = np.clip(np.full(pdn.n, pdn.node_cap[0] / pdn.n), pdn.dev_l, pdn.dev_u)
    offs = orch._offsets()
    dcap, _, _ = orch._effective_domain_caps()
    for k in range(orch.k):
        sl = slice(int(offs[k]), int(offs[k + 1]))
        tot, lmin = a[sl].sum(), pdn.dev_l[sl].sum()
        if tot > dcap[k]:
            a[sl] = pdn.dev_l[sl] + (a[sl] - pdn.dev_l[sl]) * (
                max(dcap[k] - lmin, 0.0) / max(tot - lmin, 1e-30)
            )
    assert a[t_of == 0].sum() < 0.7 * umax - 1.0


# ---------------------------------------------------------------------------
# churn + grant changes: minimum preservation, zero recompiles
# ---------------------------------------------------------------------------


def test_sla_churn_and_grants_zero_retrace(slack_pdn):
    """Leave/rejoin on a cross-cut tenant and runtime grant changes re-pin
    traced arrays only: zero recompiles, minimums preserved throughout."""
    lay = _layout(slack_pdn, lo_frac=0.4)
    orch = FleetOrchestrator(
        slack_pdn, level=1, tenants=lay, mode="stacked", options=OPTS
    )
    life = FleetLifecycle(orch)
    t_of = lay.tenant_of
    tele = np.random.default_rng(8).uniform(500, 690, slack_pdn.n)
    orch.step(tele)
    orch.step(tele)  # compile cold + warm-carry variants
    f0, e0 = orch_mod.trace_count(), engine_mod.trace_count()
    # a cross-cut tenant loses one device in each domain; the remaining
    # slice capacity still funds b_min, so the split reroutes the minimum
    life.device_leave([1, 17])
    res = orch.step(tele)
    np.testing.assert_allclose(res.allocation[[1, 17]], 0.0)
    assert res.allocation[t_of == 0].sum() >= lay.b_min[0] - 1e-4
    life.device_join([1, 17])
    res = orch.step(tele)
    assert res.allocation[t_of == 0].sum() >= lay.b_min[0] - 1e-4
    # runtime grant change: tighter minimum, lower maximum
    orch.set_tenant_bounds(0, b_min=0.5 * 2800.0, b_max=0.52 * 2800.0)
    res = orch.step(tele)
    s = res.allocation[t_of == 0].sum()
    assert 0.5 * 2800.0 - 1e-4 <= s <= 0.52 * 2800.0 + 1e-4
    assert orch_mod.trace_count() - f0 == 0  # acceptance: no recompile
    assert engine_mod.trace_count() - e0 == 0
    assert life.n_left == 0


def test_loop_sla_grants_zero_engine_retrace(slack_pdn):
    lay = _layout(slack_pdn)
    orch = FleetOrchestrator(slack_pdn, level=1, tenants=lay, mode="loop", options=OPTS)
    tele = np.random.default_rng(9).uniform(500, 690, slack_pdn.n)
    orch.step(tele)
    orch.step(tele)
    e0 = engine_mod.trace_count()
    orch.set_tenant_bounds(0, b_max=0.6 * 2800.0)
    res = orch.step(tele)
    assert engine_mod.trace_count() - e0 == 0
    assert res.allocation[lay.tenant_of == 0].sum() <= 0.6 * 2800.0 + 1e-4


def test_leave_that_kills_tenant_minimum_rejected(slack_pdn):
    """Masking out so many of a cross-cut tenant's devices that its
    minimum becomes undeliverable fails loudly at the leave — atomically,
    before any domain is re-pinned."""
    lay = _layout(slack_pdn, lo_frac=0.8, hi_frac=0.9)  # b_min 2240 W of 2800
    orch = FleetOrchestrator(
        slack_pdn, level=1, tenants=lay, mode="stacked", options=OPTS
    )
    life = FleetLifecycle(orch)
    with pytest.raises(ValueError, match="deliverable maximum"):
        life.device_leave([0, 1])  # drops umax to 1400 W < 2520 W
    assert life.n_left == 0  # nothing recorded, nothing masked
    np.testing.assert_array_equal(orch._dev_u[0][:2], slack_pdn.dev_u[:2])
    res = orch.step(np.full(slack_pdn.n, 650.0))  # still serves cleanly
    assert res.allocation[lay.tenant_of == 0].sum() >= lay.b_min[0] - 1e-4


def test_set_tenant_bounds_validates_before_commit(slack_pdn):
    lay = _layout(slack_pdn)
    orch = FleetOrchestrator(slack_pdn, level=1, tenants=lay, options=OPTS)
    with pytest.raises(ValueError, match="deliverable maximum"):
        orch.set_tenant_bounds(0, b_min=3000.0, b_max=3500.0)  # umax 2800
    assert orch._sla.b_min[0] == lay.b_min[0]  # nothing committed
    with pytest.raises(ValueError, match="b_min <= b_max"):
        orch.set_tenant_bounds(0, b_min=2000.0, b_max=1000.0)


def test_rebuild_domain_updates_tenant_membership(slack_pdn):
    """A structural rebuild atomically rewrites the domain's tenant
    membership; a tenant left with devices in one domain only reverts to
    a domain-local SLA row."""
    lay = _layout(slack_pdn)
    orch = FleetOrchestrator(
        slack_pdn, level=1, tenants=lay, mode="stacked", options=OPTS
    )
    d1 = orch.partition.domains[1]
    # rebuild domain 1 with the same topology but no tenant devices:
    # tenant 0 keeps only its domain-0 devices -> becomes domain-local
    orch.rebuild_domain(1, d1.pdn)
    assert not orch._sla.cross.any()
    assert orch._sla.n_slices == 0
    assert orch._sla.rows[1].shape[0] == 0
    res = orch.step(np.full(orch.n, 650.0))
    # tenant 0's row is now enforced over its remaining (domain-0) devices
    s = res.allocation[:2].sum()
    assert lay.b_min[0] - 1e-4 <= s  # b_min still demanded of the 2 devices
    # re-attach the two domain-1 devices to tenant 0 via rebuild
    t_of1 = np.full(d1.pdn.n, -1, np.int32)
    t_of1[[0, 1]] = 0
    orch.rebuild_domain(1, d1.pdn, tenant_of=t_of1)
    assert orch._sla.cross.tolist() == [True, False]
    res = orch.step(np.full(orch.n, 650.0))
    assert res.allocation[lay.tenant_of == 0].sum() >= lay.b_min[0] - 1e-4


def test_rebuild_orphaning_contracted_tenant_rejected(slack_pdn):
    """A rebuild that would drop the last devices of a tenant with a
    positive contractual minimum fails loudly — the contract cannot go
    silently unenforced — and leaves all state untouched."""
    lay = _layout(slack_pdn)  # tenant 1 is domain-local to domain 0
    orch = FleetOrchestrator(
        slack_pdn, level=1, tenants=lay, mode="stacked", options=OPTS
    )
    d0 = orch.partition.domains[0]
    with pytest.raises(ValueError, match="no devices"):
        orch.rebuild_domain(0, d0.pdn)  # default tenant_of: orphans tenant 1
    assert orch._sla.rows[0].tolist() == [0, 1]  # nothing committed
    # relaxing the contract first makes the same rebuild legal
    orch.set_tenant_bounds(1, b_min=0.0)
    orch.rebuild_domain(0, d0.pdn)
    res = orch.step(np.full(orch.n, 650.0))
    assert res.stats["converged"].all()


def test_domain_failover_evacuates_tenants(slack_pdn):
    """Domain-failover beyond brownout (ISSUE 6): a domain dies outright —
    its hardware draws nothing and its feed is derated to zero — and
    ``rebuild_domain`` evacuates its tenant devices so the cross-cut
    tenant's full contractual minimum is served by the surviving domain.
    The whole failover (and the later recovery) re-pins traced arrays
    only: zero recompiles in stacked mode."""
    import dataclasses as dc

    lay = _layout(slack_pdn, lo_frac=0.4)  # b_min 1120 W <= 1400 W (dom 0)
    orch = FleetOrchestrator(
        slack_pdn, level=1, tenants=lay, mode="stacked", options=OPTS
    )
    t_of = lay.tenant_of
    tele = np.random.default_rng(12).uniform(500, 690, slack_pdn.n)
    orch.step(tele)
    orch.step(tele)  # compile cold + warm-carry variants
    f0, e0 = orch_mod.trace_count(), engine_mod.trace_count()
    # domain 1 dies: dead hardware has no floors, carries no tenants
    d1 = orch.partition.domains[1]
    dead = dc.replace(d1.pdn, dev_l=np.zeros_like(d1.pdn.dev_l))
    orch.rebuild_domain(1, dead)  # default tenant_of: evacuates tenant 0
    orch.set_domain_supply(1, 0.0)  # the dead feed grants nothing
    res = orch.step(tele)
    offs = orch._offsets()
    np.testing.assert_allclose(res.allocation[offs[1] :], 0.0, atol=1e-9)
    # the evacuated tenant's minimum is served entirely by domain 0
    s = res.allocation[: offs[1]][t_of[: offs[1]] == 0].sum()
    assert s >= lay.b_min[0] - 1e-4
    assert res.stats["converged"].all()
    # recovery: feed restored, replacement hardware re-hosts the tenant
    orch.set_domain_supply(1, 1.0)
    t_of1 = np.full(d1.pdn.n, -1, np.int32)
    t_of1[[0, 1]] = 0
    orch.rebuild_domain(1, d1.pdn, tenant_of=t_of1)
    assert orch._sla.cross.tolist() == [True, False]
    res = orch.step(tele)
    assert res.allocation[t_of == 0].sum() >= lay.b_min[0] - 1e-4
    assert orch_mod.trace_count() - f0 == 0  # failover never recompiles
    assert engine_mod.trace_count() - e0 == 0


def test_loop_raise_tenant_minimum_from_zero(slack_pdn):
    """Loop-mode engines must accept SLA lower bounds raised from zero at
    runtime (the pin-free simplification stays off for SLA domains)."""
    lay = _layout(slack_pdn, lo_frac=0.0)  # all contracts start at b_min=0
    orch = FleetOrchestrator(slack_pdn, level=1, tenants=lay, mode="loop", options=OPTS)
    tele = np.random.default_rng(11).uniform(250, 400, slack_pdn.n)
    orch.step(tele)
    orch.set_tenant_bounds(0, b_min=0.45 * 2800.0)  # raise cross-cut min
    res = orch.step(tele)  # must not trip the engine pin-free guard
    assert res.allocation[lay.tenant_of == 0].sum() >= 0.45 * 2800.0 - 1e-4


def test_engine_pin_free_guard():
    """An engine compiled under the pin-free simplification refuses SLA
    lower bounds that would invalidate it."""
    from repro.core.treeops import SlaTopo

    pdn = homogeneous_fleet(1, domain_oversub=1.15)
    sla = SlaTopo(
        dev=np.arange(4, dtype=np.int32),
        ten=np.zeros(4, np.int32),
        lo=np.zeros(1),
        hi=np.array([2000.0]),
    )
    eng = AllocEngine(pdn, sla=sla)
    assert eng.meta.pin_free
    eng.set_sla_bounds(np.zeros(1), np.array([1800.0]))  # lo stays 0: fine
    with pytest.raises(ValueError, match="pin-free"):
        eng.set_sla_bounds(np.array([500.0]), np.array([1800.0]))
