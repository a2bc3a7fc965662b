"""System-level invariants of the full nvPAX policy (Algorithm 3).

These encode the paper's Requirements 1-6 (section 3) as executable
properties: deterministic feasibility, closeness to requests, utilization
maximization, idle/active prioritization, priority ordering, and fairness.
"""

from __future__ import annotations

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

import jax
import jax.numpy as jnp

from repro.core.metrics import satisfaction_ratio
from repro.core.nvpax import optimize
from repro.core.greedy import static_allocate
from repro.core.problem import AllocProblem
from repro.core.treeops import sla_matvec
from repro.pdn.tenants import assign_tenants
from repro.pdn.tree import build_from_level_sizes


def assert_feasible(pdn, ap, a, tol=1e-6):
    """Requirement 1: every physical + SLA constraint holds."""
    assert (a >= pdn.dev_l - tol).all(), "box lower violated"
    assert (a <= pdn.dev_u + tol).all(), "box upper violated"
    csum = np.concatenate([[0.0], np.cumsum(a)])
    sums = csum[pdn.node_end] - csum[pdn.node_start]
    assert (sums <= pdn.node_cap + tol).all(), "tree capacity violated"
    if ap.sla.k:
        ten = np.asarray(sla_matvec(jnp.asarray(a), ap.sla))
        assert (ten >= np.asarray(ap.sla.lo) - tol).all(), "SLA lower violated"
        assert (ten <= np.asarray(ap.sla.hi) + tol).all(), "SLA upper violated"


# one fixed PDN shape so the jitted solver compiles once for the whole
# hypothesis run (shapes are static args of the jit)
_PDN = build_from_level_sizes([2, 2, 2], gpus_per_server=4)  # 32 devices


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_always_feasible_random_requests(seed):
    rng = np.random.default_rng(seed)
    req = rng.uniform(0, 900, _PDN.n)  # deliberately outside [l, u] too
    ap = AllocProblem.build(_PDN, req)
    res = optimize(ap)
    assert_feasible(_PDN, ap, res.allocation)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_dominates_static_every_step(seed):
    """Paper section 5.5: nvPAX was at least as good as Static on every
    timestamp."""
    rng = np.random.default_rng(seed)
    req = rng.uniform(50, 800, _PDN.n)
    ap = AllocProblem.build(_PDN, req)
    res = optimize(ap)
    r = np.asarray(ap.r)
    s_nv = satisfaction_ratio(r, res.allocation)
    s_st = satisfaction_ratio(r, static_allocate(_PDN))
    assert s_nv >= s_st - 1e-6


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_phases_monotone(seed):
    """Phase II only raises active devices; Phase III only raises idle."""
    rng = np.random.default_rng(seed)
    req = rng.uniform(50, 600, _PDN.n)
    ap = AllocProblem.build(_PDN, req)
    res = optimize(ap)
    act = np.asarray(ap.active)
    assert (res.phase2 - res.phase1 >= -1e-6).all()
    np.testing.assert_allclose(res.phase2[~act], res.phase1[~act], atol=1e-6)
    assert (res.allocation - res.phase2 >= -1e-6).all()
    np.testing.assert_allclose(res.allocation[act], res.phase2[act], atol=1e-6)


def test_idle_devices_get_at_least_minimum():
    req = np.full(_PDN.n, 50.0)  # everyone idle
    ap = AllocProblem.build(_PDN, req)
    res = optimize(ap)
    assert (res.allocation >= _PDN.dev_l - 1e-9).all()
    # Phase I leaves idle at l; Phase III then fills leftover root budget
    np.testing.assert_allclose(res.phase1, _PDN.dev_l, atol=1e-6)


def test_priority_ordering():
    """Under shortage, higher-priority devices are satisfied first."""
    # tight root: only ~half the extra demand fits
    from repro.pdn.tree import PDNNode, flatten

    root = PDNNode(capacity=3000.0)
    root.add(PDNNode(capacity=2800.0, n_devices=4))
    root.add(PDNNode(capacity=2800.0, n_devices=4))
    pdn = flatten(root, default_l=100.0, default_u=700.0)
    req = np.full(8, 650.0)
    prio = np.array([2, 2, 1, 1, 2, 2, 1, 1], np.int32)
    ap = AllocProblem.build(
        pdn, req, active=np.ones(8, bool), priority=prio
    )
    res = optimize(ap)
    a = res.allocation
    hi = a[prio == 2]
    lo = a[prio == 1]
    # high priority fully satisfied, low priority absorbs the shortage evenly
    np.testing.assert_allclose(hi, 650.0, atol=0.5)
    np.testing.assert_allclose(lo, lo.mean(), atol=0.5)  # fair within level
    assert lo.mean() < 200.0 + (3000 - 4 * 650 - 4 * 100) / 4 + 1


def test_fair_shortage_within_level(tiny_pdn):
    """Requirement 6: within a priority level, deviation from requests is
    spread evenly (here: symmetric devices get identical allocations)."""
    req = np.full(tiny_pdn.n, 690.0)  # symmetric heavy demand
    ap = AllocProblem.build(tiny_pdn, req, active=np.ones(tiny_pdn.n, bool))
    res = optimize(ap)
    np.testing.assert_allclose(res.allocation, res.allocation[0], atol=0.5)


def test_surplus_distributed_fairly(tiny_pdn):
    """Phase II max-min: symmetric active devices receive equal raises."""
    req = np.full(tiny_pdn.n, 300.0)
    ap = AllocProblem.build(tiny_pdn, req, active=np.ones(tiny_pdn.n, bool))
    res = optimize(ap)
    raise_ = res.phase2 - res.phase1
    np.testing.assert_allclose(raise_, raise_[0], atol=0.5)
    assert raise_[0] > 0  # there IS surplus on this geometry


def test_no_reserved_budget_when_demand_exceeds():
    """Requirement 3: with demand everywhere, the root budget is used up."""
    pdn = build_from_level_sizes([2, 2], gpus_per_server=4)
    req = np.full(pdn.n, 700.0)
    ap = AllocProblem.build(pdn, req, active=np.ones(pdn.n, bool))
    res = optimize(ap)
    used = res.allocation.sum()
    # every node on the root-to-leaf path may bind first; check the binding
    # level is saturated
    csum = np.concatenate([[0.0], np.cumsum(res.allocation)])
    sums = csum[pdn.node_end] - csum[pdn.node_start]
    slack = pdn.node_cap - sums
    # for the uniform tree the racks bind: every leaf is under a tight node
    tight = slack <= 1e-3
    covered = np.zeros(pdn.n, bool)
    for j in np.nonzero(tight)[0]:
        covered[pdn.node_start[j] : pdn.node_end[j]] = True
    at_u = res.allocation >= pdn.dev_u - 1e-3
    assert (covered | at_u).all(), "some device could still be raised"


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_sla_constraints_enforced(seed):
    """Requirement 1 (service level): tenant bounds hold for random scattered
    tenants."""
    pdn = _PDN
    lay = assign_tenants(
        pdn, n_tenants=2, devices_per_tenant=6, seed=seed, lo_frac=0.35,
        hi_frac=0.75,
    )
    rng = np.random.default_rng(seed)
    req = rng.uniform(50, 800, pdn.n)
    ap = AllocProblem.build(pdn, req, sla=lay.sla_topo(), priority=lay.priority)
    res = optimize(ap)
    assert_feasible(pdn, ap, res.allocation, tol=1e-4)


def test_sla_lower_bound_forces_idle_up():
    """A tenant minimum above the idle fleet's l forces allocations up even
    for idle devices (the eps-regularizer scenario of eq. 4)."""
    pdn = build_from_level_sizes([2, 2], gpus_per_server=4)  # 16 devices
    from repro.core.treeops import SlaTopo

    with jax.enable_x64(True):
        sla = SlaTopo(
            dev=jnp.arange(4, dtype=jnp.int32),
            ten=jnp.zeros(4, jnp.int32),
            lo=jnp.asarray([4 * 400.0]),
            hi=jnp.asarray([np.inf]),
        )
    req = np.full(pdn.n, 50.0)  # all idle
    ap = AllocProblem.build(pdn, req, sla=sla)
    res = optimize(ap)
    assert res.allocation[:4].sum() >= 4 * 400.0 - 1e-3
    # devices outside the tenant stay near their minimum at Phase 1
    np.testing.assert_allclose(res.phase1[4:], pdn.dev_l[4:], atol=1.0)


def test_deterministic():
    req = np.random.default_rng(11).uniform(50, 800, _PDN.n)
    ap = AllocProblem.build(_PDN, req)
    a1 = optimize(ap).allocation
    a2 = optimize(ap).allocation
    np.testing.assert_array_equal(a1, a2)


def test_closeness_to_requests_when_feasible(tiny_pdn):
    """With ample capacity, Phase I returns exactly the requests."""
    req = np.full(tiny_pdn.n, 250.0)
    ap = AllocProblem.build(tiny_pdn, req, active=np.ones(tiny_pdn.n, bool))
    res = optimize(ap)
    np.testing.assert_allclose(res.phase1, 250.0, atol=0.05)
