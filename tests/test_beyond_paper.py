"""Beyond-paper features: the anytime/deadline-aware variant (paper
section 6 future work) and vmap-over-scenarios batched solving (MPC /
what-if evaluation on one accelerator program)."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import phases, solver
from repro.core.nvpax import NvpaxOptions, optimize
from repro.core.problem import AllocProblem
from repro.pdn.tree import build_from_level_sizes


@pytest.fixture(scope="module")
def pdn():
    return build_from_level_sizes([2, 3, 2], gpus_per_server=4)


def test_anytime_zero_deadline_truncates_to_phase1(pdn):
    """With an already-expired deadline, phases II/III are skipped but the
    result is still feasible and satisfies Phase I semantics."""
    req = np.random.default_rng(0).uniform(150, 450, pdn.n)
    ap = AllocProblem.build(pdn, req)
    res = optimize(ap, NvpaxOptions(deadline_s=0.0))
    assert res.stats["truncated"]
    np.testing.assert_allclose(res.allocation, res.phase1, atol=1e-9)
    # feasibility is never sacrificed
    csum = np.concatenate([[0.0], np.cumsum(res.allocation)])
    sums = csum[pdn.node_end] - csum[pdn.node_start]
    assert (sums <= pdn.node_cap + 1e-6).all()


def test_anytime_generous_deadline_matches_full(pdn):
    req = np.random.default_rng(1).uniform(150, 450, pdn.n)
    ap = AllocProblem.build(pdn, req)
    full = optimize(ap)
    timed = optimize(ap, NvpaxOptions(deadline_s=120.0))
    assert not timed.stats["truncated"]
    np.testing.assert_allclose(timed.allocation, full.allocation, atol=1e-9)


def test_anytime_is_monotone_refinement(pdn):
    """phase1 <= phase2 <= final pointwise: each deadline tier returns a
    refinement (more surplus distributed), never a regression."""
    req = np.random.default_rng(2).uniform(150, 400, pdn.n)
    ap = AllocProblem.build(pdn, req)
    res = optimize(ap)
    assert (res.phase2 - res.phase1 >= -1e-9).all()
    assert (res.allocation - res.phase2 >= -1e-9).all()


def test_vmap_over_scenarios(pdn):
    """The jitted solver vmaps over request scenarios (MPC what-if): one
    compiled program evaluates K candidate futures; results match
    per-scenario solves."""
    with jax.enable_x64(True):
        rng = np.random.default_rng(3)
        K = 3
        reqs = rng.uniform(150, 650, (K, pdn.n))
        aps = [AllocProblem.build(pdn, r) for r in reqs]
        tree, sla = aps[0].tree, aps[0].sla

        def solve_one(r_vec):
            ap0 = aps[0]
            prob = phases.qp_step(
                ap0._replace(r=r_vec), ap0.l, ap0.active, jnp.zeros(ap0.n, bool),
                1e-5, pin_free=True,
            )
            st = solver.SolverState.zeros(ap0.n, tree.m, sla.k, jnp.float64)
            st, stats = solver.solve(prob, tree, sla, st)
            return st.x, stats.converged

        # NOTE: active masks differ between scenarios; use scenario 0's
        # activity for all (what-if on demand levels, same job placement)
        xs, convs = jax.vmap(solve_one)(jnp.asarray(
            np.clip(reqs, pdn.dev_l, pdn.dev_u)))
        assert xs.shape == (K, pdn.n)
        for i in range(K):
            xi, ci = solve_one(jnp.asarray(np.clip(reqs[i], pdn.dev_l, pdn.dev_u)))
            np.testing.assert_allclose(
                np.asarray(xs[i]), np.asarray(xi), atol=0.6,
            )
