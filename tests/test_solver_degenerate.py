"""Degenerate-geometry regression suite for the solver core (ISSUE 5).

The geometries here were the top open ROADMAP item since PR 4: node caps
exactly equal to subtree maxima (``oversubscription=1.0``) and
eps-tie-broken max-min objectives (``lp_step``'s ±eps terms over identical
boxes).  Pre-overhaul, Phase II/III rounds reached the optimal vertex in a
couple thousand iterations but PDHG could not certify KKT within 50k — the
scalar ``t`` froze above its optimum while the improvement-row duals
tugged-of-war.  The :mod:`repro.core.solver` package must now exit these
rounds within a small iteration budget on every path (host, batched,
engine), via genuine KKT certification (adaptive restarts re-estimate the
primal weight) or the no-progress/optimal-vertex certificate (exact
epigraph t-polish).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import phases, solver
from repro.core.batched import optimize_batched
from repro.core.engine import AllocEngine, trace_count
from repro.core.nvpax import NvpaxOptions, optimize
from repro.core.problem import AllocProblem
from repro.pdn.tenants import assign_tenants
from repro.pdn.tree import build_from_level_sizes

pytestmark = pytest.mark.usefixtures("x64")

# acceptance bound (ISSUE 5): a degenerate max-min round must exit with a
# certificate within this many PDHG iterations (the pre-overhaul solver
# burned its full 50k budget without one)
CERT_BUDGET = 5_000


def degenerate_problem(seed=0, ties=False):
    """Caps exactly equal to subtree maxima + tenant rows; ``ties=True``
    additionally makes every request identical (exactly tied objectives)."""
    pdn = build_from_level_sizes([2, 2], gpus_per_server=4, oversubscription=1.0)
    lay = assign_tenants(
        pdn,
        n_tenants=2,
        devices_per_tenant=4,
        hi_frac=1.0 if ties else 0.8,
        seed=seed,
    )
    if ties:
        tele = np.full(pdn.n, 660.0)
    else:
        tele = np.random.default_rng(seed).uniform(600, 690, pdn.n)
    ap = AllocProblem.build(pdn, tele, sla=lay.sla_topo(), priority=lay.priority)
    return pdn, lay, tele, ap


def degenerate_lp(ap):
    """The Phase II max-min LP after a converged Phase I."""
    res = optimize(ap, NvpaxOptions(run_phase2=False, run_phase3=False))
    assert res.stats["converged"]
    x1, state = jnp.asarray(res.phase1), res.warm_state.p1
    mask_a = ap.active & ~phases.saturated_mask(x1, ap, ap.active)
    assert bool(np.asarray(mask_a).any())
    prob = phases.lp_step(ap, x1, mask_a, ~(mask_a | ap.idle), ap.idle, 1e-5)
    warm = solver.SolverState(x1, jnp.zeros(()), state.y_tree, state.y_sla, state.y_imp)
    return prob, warm


@pytest.mark.parametrize("ties", [False, True])
def test_degenerate_lp_certifies_within_budget(ties):
    """Adaptive restarts walk the primal weight to the regime that actually
    certifies KKT — within the budget, with the HiGHS optimum."""
    from repro.core.refsolve import ref_solve

    _, _, _, ap = degenerate_problem(ties=ties)
    prob, warm = degenerate_lp(ap)
    st, stats = solver.solve(prob, ap.tree, ap.sla, warm)
    assert bool(stats.converged)
    assert int(stats.iterations) <= CERT_BUDGET
    assert int(stats.restarts) >= 1
    zref = ref_solve(prob, ap.tree, ap.sla)
    np.testing.assert_allclose(np.asarray(st.x), zref[: ap.n], atol=1e-3)
    assert abs(float(st.t) - zref[-1]) <= 1e-3 * (1.0 + abs(zref[-1]))


def test_degenerate_vertex_certificate_polishes_t():
    """With adaptive restarts off, the fixed-cadence solver still cannot
    certify KKT — the no-progress certificate must exit within budget and
    the epigraph polish must return the *exact* optimal t for the settled
    vertex (the pre-overhaul solver returned t inflated by ~3.5 W here)."""
    from repro.core.refsolve import ref_solve

    _, _, _, ap = degenerate_problem()
    prob, warm = degenerate_lp(ap)
    st, stats = solver.solve(
        prob,
        ap.tree,
        ap.sla,
        warm,
        solver.SolverOptions(adaptive_restarts=False),
    )
    assert bool(stats.converged)
    assert not bool(stats.certified)  # exited on the certificate, not KKT
    assert int(stats.iterations) <= CERT_BUDGET
    zref = ref_solve(prob, ap.tree, ap.sla)
    np.testing.assert_allclose(np.asarray(st.x), zref[: ap.n], atol=1e-6)
    assert abs(float(st.t) - zref[-1]) <= 1e-6 * (1.0 + abs(zref[-1]))


def test_degenerate_three_phase_paths_agree_and_certify():
    """Host, batched and engine paths all exit the degenerate fixture within
    a bounded iteration count and agree to <= 1e-6 W."""
    pdn, lay, tele, ap = degenerate_problem()

    host = optimize(ap)
    assert host.stats["converged"]
    assert host.stats["total_iterations"] <= 3 * CERT_BUDGET

    batched = optimize_batched([ap, ap])
    assert bool(np.asarray(batched.stats["converged"]).all())
    assert int(np.asarray(batched.stats["iterations"]).max()) <= 3 * CERT_BUDGET
    np.testing.assert_allclose(batched.allocation[0], host.allocation, atol=1e-6)
    np.testing.assert_allclose(batched.allocation[1], batched.allocation[0], atol=1e-12)

    eng = AllocEngine(pdn, sla=lay.sla_topo(), priority=lay.priority)
    r1 = eng.step(tele)
    assert r1.stats["converged"]
    assert r1.stats["total_iterations"] <= 3 * CERT_BUDGET
    np.testing.assert_allclose(r1.allocation, host.allocation, atol=1e-6)

    # steady-state warm steps re-certify without recompiling anything (the
    # cold and warm-carry steps are two jit variants, so prime both first)
    eng.step(tele)
    n0 = trace_count()
    r2 = eng.step(tele)
    assert trace_count() == n0
    assert r2.stats["converged"]
    assert r2.stats["total_iterations"] <= 3 * CERT_BUDGET
    np.testing.assert_allclose(r2.allocation, host.allocation, atol=1e-6)


def test_degenerate_warm_brownout_preserves_minimums():
    """A warm-carried brownout step on the degenerate fleet: tenant
    minimums must hold because the rounds now *converge* — not because the
    monotone truncation clamp caught a stalled solve (the pre-overhaul
    behavior this fixture pins down)."""
    pdn, lay, tele, _ = degenerate_problem(seed=3)
    eng = AllocEngine(pdn, sla=lay.sla_topo(), priority=lay.priority)
    eng.step(tele)
    # derate the root feed 10% mid-trace, carrying warm state across the
    # change like the fleet coordinator's per-step grants do
    eng.set_root_cap(0.9 * float(pdn.node_cap[0]), reset_warm=False)
    res = eng.step(tele)
    assert res.stats["converged"]
    assert res.stats["total_iterations"] <= 3 * CERT_BUDGET
    lo = np.asarray(lay.sla_topo().lo)
    for t in range(lay.n_tenants):
        got = res.allocation[lay.tenant_of == t].sum()
        assert got >= lo[t] - 1e-6, f"tenant {t} below minimum after brownout"


def test_phase_cost_model_mix_weighting():
    """The deadline budget now prices phases separately: a Phase-I-heavy mix
    must yield a different budget than a max-min-heavy mix when the phase
    prices differ."""
    from repro.core.batched import PhaseCostModel

    model = PhaseCostModel(p1_s=1e-4, p23_s=2e-4, mix=(0.5, 0.5))
    b_default = model.budget(1.0)
    b_p1 = model.budget(1.0, mix=(1.0, 0.0))
    b_p23 = model.budget(1.0, mix=(0.0, 1.0))
    assert b_p1 > b_default > b_p23
    assert b_p1 == int(1.0 / 1e-4)
    assert b_p23 == int(1.0 / 2e-4)
