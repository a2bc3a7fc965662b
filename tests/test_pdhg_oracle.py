"""Cross-validation of the PDHG solver against the paper's solver stack.

The paper solves LPs with HiGHS; ``scipy.optimize.linprog`` *is* HiGHS, so
the LP comparisons here pit our TPU-native solver against the paper's own
engine.  QPs are compared on objective value (the eps-regularized blocks are
near-degenerate, so coordinatewise comparison is not meaningful — both
solvers may pick different optima within tolerance).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import phases, solver
from repro.core.nvpax import NvpaxOptions, optimize
from repro.core.problem import AllocProblem
from repro.core.refsolve import ref_solve
from repro.pdn.hierarchy_gen import random_hierarchy
from repro.pdn.tenants import assign_tenants

pytestmark = pytest.mark.usefixtures("x64")


def _qp_objective(prob, x):
    w = np.asarray(prob.w)
    t = np.asarray(prob.target)
    return 0.5 * np.sum(w * (x - t) ** 2) + np.asarray(prob.c) @ x


def _build(seed, n=40, with_sla=True):
    pdn = random_hierarchy(n, seed=seed, depth=3)
    if with_sla:
        lay = assign_tenants(
            pdn, n_tenants=2, devices_per_tenant=min(8, n // 4), seed=seed
        )
        sla, prio = lay.sla_topo(), lay.priority
    else:
        sla, prio = None, None
    req = np.random.default_rng(seed).uniform(50, 800, pdn.n)
    return pdn, AllocProblem.build(pdn, req, sla=sla, priority=prio)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("with_sla", [False, True])
def test_phase1_qp_objective_matches_oracle(seed, with_sla):
    _, ap = _build(seed, with_sla=with_sla)
    p = int(np.asarray(ap.priority).max())
    mask_a = ap.active & (ap.priority == p)
    prob = phases.qp_step(
        ap, ap.l, mask_a, jnp.zeros(ap.n, bool), 1e-5, pin_free=not with_sla
    )
    st = solver.SolverState.zeros(ap.n, ap.tree.m, ap.sla.k, jnp.float64)
    st, stats = solver.solve(prob, ap.tree, ap.sla, st)
    assert bool(stats.converged), "PDHG did not converge"
    zref = ref_solve(prob, ap.tree, ap.sla)
    obj_pdhg = _qp_objective(prob, np.asarray(st.x))
    obj_ref = _qp_objective(prob, zref[: ap.n])
    # PDHG must be no worse than the scipy solution (up to tolerance); both
    # must agree on the strictly-convex request-tracking block.
    scale = 1.0 + abs(obj_ref)
    assert obj_pdhg <= obj_ref + 1e-4 * scale
    a_block = np.asarray(mask_a)
    np.testing.assert_allclose(
        np.asarray(st.x)[a_block], zref[: ap.n][a_block], atol=0.5
    )


@pytest.mark.parametrize("seed", [0, 1])
def test_maxmin_lp_matches_highs(seed):
    """The Phase II LP optimum t* (unique) must match HiGHS."""
    _, ap = _build(seed, with_sla=True)
    res = optimize(ap, NvpaxOptions(run_phase2=False, run_phase3=False))
    x1, state = jnp.asarray(res.phase1), res.warm_state.p1
    mask_a = ap.active & ~phases.saturated_mask(x1, ap, ap.active)
    if not bool(np.asarray(mask_a).any()):
        pytest.skip("no unsaturated active devices on this seed")
    prob = phases.lp_step(ap, x1, mask_a, ~(mask_a | ap.idle), ap.idle, 1e-5)
    st = solver.SolverState(
        x1, jnp.zeros(()), state.y_tree, state.y_sla, state.y_imp
    )
    st, stats = solver.solve(prob, ap.tree, ap.sla, st)
    assert bool(stats.converged)
    zref = ref_solve(prob, ap.tree, ap.sla)
    t_ref = zref[-1]
    assert abs(float(st.t) - t_ref) < 0.05 * (1.0 + abs(t_ref))


def test_lp_epigraph_bounds_respected(tiny_pdn):
    """Improvement rows a_i - t >= base_i hold at the LP solution."""
    req = np.random.default_rng(3).uniform(100, 700, tiny_pdn.n)
    ap = AllocProblem.build(tiny_pdn, req)
    res = optimize(ap, NvpaxOptions(run_phase2=False, run_phase3=False))
    x1, state = jnp.asarray(res.phase1), res.warm_state.p1
    mask_a = ap.active & ~phases.saturated_mask(x1, ap, ap.active)
    if not bool(np.asarray(mask_a).any()):
        pytest.skip("all saturated")
    prob = phases.lp_step(ap, x1, mask_a, ~(mask_a | ap.idle), ap.idle, 1e-5)
    st = solver.SolverState(x1, jnp.zeros(()), state.y_tree, state.y_sla, state.y_imp)
    st, stats = solver.solve(prob, ap.tree, ap.sla, st)
    x, t = np.asarray(st.x), float(st.t)
    sel = np.asarray(mask_a)
    assert (x[sel] - np.asarray(x1)[sel] >= t - 1e-4).all()
    assert t >= -1e-9


def test_warm_start_reduces_iterations(small_pdn):
    """Re-solving a perturbed problem from the previous state must take
    fewer iterations than from scratch (paper section 5.6 future work —
    implemented here)."""
    rng = np.random.default_rng(7)
    req = rng.uniform(100, 700, small_pdn.n)
    ap = AllocProblem.build(small_pdn, req)
    prob = phases.qp_step(
        ap, ap.l, ap.active, jnp.zeros(ap.n, bool), 1e-5, pin_free=True
    )
    cold = solver.SolverState.zeros(ap.n, ap.tree.m, ap.sla.k, jnp.float64)
    st, stats_cold = solver.solve(prob, ap.tree, ap.sla, cold)
    # perturb requests slightly (next control step)
    req2 = req + rng.normal(0, 5.0, small_pdn.n)
    ap2 = AllocProblem.build(small_pdn, req2)
    prob2 = phases.qp_step(
        ap2, ap2.l, ap2.active, jnp.zeros(ap2.n, bool), 1e-5, pin_free=True
    )
    st2, stats_warm = solver.solve(prob2, ap2.tree, ap2.sla, st)
    assert bool(stats_warm.converged)
    assert int(stats_warm.iterations) <= int(stats_cold.iterations)


def test_pinned_variables_stay_pinned(tiny_pdn):
    req = np.random.default_rng(5).uniform(100, 700, tiny_pdn.n)
    ap = AllocProblem.build(tiny_pdn, req)
    pin_mask = jnp.asarray([True, False, False, False, True, False, False, False])
    pin_val = jnp.full((8,), 333.0)
    mask_a = ap.active & ~pin_mask
    prob = phases.qp_step(ap, pin_val, mask_a, pin_mask, 1e-5, pin_free=True)
    st = solver.SolverState.zeros(ap.n, ap.tree.m, ap.sla.k, jnp.float64)
    st, stats = solver.solve(prob, ap.tree, ap.sla, st)
    x = np.asarray(st.x)
    np.testing.assert_allclose(x[[0, 4]], 333.0, atol=1e-6)
