"""Appendix B mechanics at test scale: tenant SLA enforcement, margins,
priorities, and the metrics used to report them."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.metrics import (
    relative_improvement,
    satisfaction_ratio,
    sla_margin,
    tenant_satisfaction,
    useful_utilization,
)
from repro.core.nvpax import optimize
from repro.core.problem import AllocProblem
from repro.core.treeops import sla_matvec
from repro.pdn.tenants import assign_tenants
from repro.pdn.telemetry import TelemetrySim, TraceConfig
from repro.pdn.tree import build_from_level_sizes


@pytest.fixture(scope="module")
def setup():
    pdn = build_from_level_sizes([2, 4, 2], gpus_per_server=4)  # 64 devices
    lay = assign_tenants(
        pdn, n_tenants=3, devices_per_tenant=12, lo_frac=0.4, hi_frac=0.8,
        seed=0,
    )
    sim = TelemetrySim(TraceConfig(n_devices=pdn.n, seed=0))
    return pdn, lay, sim


def test_appendix_b_zero_violations(setup):
    """Paper B.3: zero min/max SLA violations across timestamps."""
    pdn, lay, sim = setup
    warm = None
    for t in range(4):
        req = sim.power(t)
        ap = AllocProblem.build(
            pdn, req, sla=lay.sla_topo(), priority=lay.priority
        )
        res = optimize(ap, warm=warm)
        warm = res.warm_state
        sums = np.asarray(sla_matvec(jnp.asarray(res.allocation), ap.sla))
        assert (sums >= lay.b_min - 1e-4).all(), f"t={t} min SLA violated"
        assert (sums <= lay.b_max + 1e-4).all(), f"t={t} max SLA violated"


def test_sla_margins_positive(setup):
    pdn, lay, sim = setup
    req = sim.power(10)
    ap = AllocProblem.build(pdn, req, sla=lay.sla_topo(), priority=lay.priority)
    res = optimize(ap)
    m = sla_margin(res.allocation, lay.tenant_of, lay.n_tenants, lay.b_min, lay.b_max)
    assert (m >= -1e-6).all()
    assert (m <= 1.0 + 1e-6).all()


def test_tenant_satisfaction_metric(setup):
    pdn, lay, sim = setup
    req = sim.power(20)
    ap = AllocProblem.build(pdn, req, sla=lay.sla_topo(), priority=lay.priority)
    res = optimize(ap)
    r = np.asarray(ap.r)
    s = tenant_satisfaction(r, res.allocation, lay.tenant_of, lay.n_tenants)
    assert ((s >= 0) & (s <= 1 + 1e-9)).all()


def test_metrics_formulas():
    r = np.array([100.0, 200.0, 300.0])
    a = np.array([150.0, 150.0, 300.0])
    assert useful_utilization(r, a) == 100 + 150 + 300
    assert satisfaction_ratio(r, a) == pytest.approx(550 / 600)
    base = np.array([100.0, 100.0, 100.0])
    assert relative_improvement(r, a, base) == pytest.approx(
        100 * (550 - 300) / 300
    )
    assert satisfaction_ratio(np.zeros(3), a) == 1.0


def test_max_only_sla_cap_enforced(setup):
    """A tenant max budget caps its aggregate below unconstrained level."""
    pdn, lay, sim = setup
    with jax.enable_x64(True):
        from repro.core.treeops import SlaTopo

        dev = jnp.arange(8, dtype=jnp.int32)
        sla = SlaTopo(
            dev=dev,
            ten=jnp.zeros(8, jnp.int32),
            lo=jnp.asarray([0.0]),
            hi=jnp.asarray([8 * 300.0]),
        )
    req = np.full(pdn.n, 650.0)
    ap = AllocProblem.build(pdn, req, active=np.ones(pdn.n, bool), sla=sla)
    res = optimize(ap)
    assert res.allocation[:8].sum() <= 8 * 300.0 + 1e-4


def test_priorities_with_tenants(setup):
    """Higher-priority tenant devices track requests closer under shortage."""
    pdn, lay, sim = setup
    req = np.full(pdn.n, 680.0)  # heavy shortage
    prio = lay.priority
    ap = AllocProblem.build(
        pdn, req, active=np.ones(pdn.n, bool), sla=lay.sla_topo(), priority=prio
    )
    res = optimize(ap)
    r = np.asarray(ap.r)
    defic = r - np.minimum(res.allocation, r)
    mean_def = [defic[prio == p].mean() for p in (1, 2, 3)]
    assert mean_def[2] <= mean_def[1] + 1e-3 <= mean_def[0] + 2e-3
