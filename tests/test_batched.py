"""Equivalence + feasibility of the fully-jitted batched engine
(:mod:`repro.core.batched`) against one lane of it.

``nvpax.optimize`` runs the same program on one scenario, so each of K
vmapped lanes must match it to solver tolerance — on tree-only problems
(waterfill fast path) and on tenant-SLA problems (iterated-LP path), with
mixed priorities and per-scenario activity patterns.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.batched import (
    BatchMeta,
    batch_meta,
    optimize_batched,
    stack_problems,
)
from repro.core.nvpax import NvpaxOptions, optimize
from repro.core.problem import AllocProblem
from repro.pdn.tenants import assign_tenants
from repro.pdn.tree import build_from_level_sizes

# one lane and K lanes execute the same program under vmap; observed
# deviation is ~1e-13 W.  1e-4 W leaves 9 orders of slack while still
# asserting "solver tolerance" equality.
ATOL = 1e-4


@pytest.fixture(scope="module")
def pdn():
    return build_from_level_sizes([2, 3, 2], gpus_per_server=4)  # n = 48


def _tree_feasible(pdn, x, tol=1e-6):
    csum = np.concatenate([[0.0], np.cumsum(x)])
    sums = csum[pdn.node_end] - csum[pdn.node_start]
    return (sums <= pdn.node_cap + tol).all()


def test_batched_matches_sequential_tree_only(pdn):
    """k = 0: scanned Phase I + jitted waterfill match the one-lane path on
    K >= 3 scenarios with differing requests AND activity patterns."""
    rng = np.random.default_rng(0)
    K = 4
    # wide request range so scenarios differ in their active sets too
    reqs = rng.uniform(50, 650, (K, pdn.n))
    aps = [AllocProblem.build(pdn, r) for r in reqs]

    res_b = optimize_batched(aps)
    assert res_b.allocation.shape == (K, pdn.n)
    assert res_b.stats["converged"].all()
    for k in range(K):
        res_s = optimize(aps[k])
        np.testing.assert_allclose(
            res_b.allocation[k], res_s.allocation, atol=ATOL,
            err_msg=f"scenario {k} final allocation",
        )
        np.testing.assert_allclose(
            res_b.phase1[k], res_s.phase1, atol=ATOL,
            err_msg=f"scenario {k} phase1",
        )
        assert _tree_feasible(pdn, res_b.allocation[k])


def test_batched_matches_sequential_sla(pdn):
    """k > 0: tenant SLAs force the iterated-LP max-min path; mixed
    priorities exercise the multi-level Phase I scan."""
    layout = assign_tenants(pdn, n_tenants=4, devices_per_tenant=8, seed=1)
    sla = layout.sla_topo()
    rng = np.random.default_rng(1)
    K = 3
    reqs = rng.uniform(100, 650, (K, pdn.n))
    aps = [
        AllocProblem.build(pdn, r, sla=sla, priority=layout.priority)
        for r in reqs
    ]

    res_b = optimize_batched(aps)
    assert res_b.stats["converged"].all()
    # multi-level sweep actually ran: priorities {1,2,3} are present
    assert len(batch_meta(stack_problems(aps), NvpaxOptions()).levels) == 3
    for k in range(K):
        res_s = optimize(aps[k])
        np.testing.assert_allclose(
            res_b.allocation[k], res_s.allocation, atol=ATOL,
            err_msg=f"scenario {k} final allocation",
        )
        assert _tree_feasible(pdn, res_b.allocation[k])
        # tenant upper bounds hold
        agg = np.zeros(layout.n_tenants)
        np.add.at(agg, layout.tenant_of[layout.tenant_of >= 0],
                  res_b.allocation[k][layout.tenant_of >= 0])
        assert (agg <= layout.b_max + 1e-6).all()


def test_batched_lp_path_matches_waterfill_path(pdn):
    """With the waterfill fast path disabled the batched LP loop converges
    to the same max-min allocation (k = 0 cross-validation)."""
    rng = np.random.default_rng(2)
    aps = [AllocProblem.build(pdn, rng.uniform(150, 500, pdn.n)) for _ in range(2)]
    res_wf = optimize_batched(aps, NvpaxOptions(use_waterfill=True))
    res_lp = optimize_batched(aps, NvpaxOptions(use_waterfill=False))
    np.testing.assert_allclose(res_wf.allocation, res_lp.allocation, atol=0.05)


def test_batched_warm_start_roundtrip(pdn):
    """warm_state from one batched call is accepted by the next and does not
    change the solution (warm start is an optimization, not semantics)."""
    rng = np.random.default_rng(3)
    aps = [AllocProblem.build(pdn, rng.uniform(100, 600, pdn.n)) for _ in range(3)]
    first = optimize_batched(aps)
    second = optimize_batched(aps, warm=first.warm_state)
    np.testing.assert_allclose(second.allocation, first.allocation, atol=ATOL)


def test_stack_problems_rejects_topology_mismatch(pdn):
    other = build_from_level_sizes([2, 2, 2], gpus_per_server=4)
    a = AllocProblem.build(pdn, np.full(pdn.n, 300.0))
    b = AllocProblem.build(other, np.full(other.n, 300.0))
    with pytest.raises(ValueError):
        stack_problems([a, b])


def test_batch_meta_is_static_and_hashable(pdn):
    a = AllocProblem.build(pdn, np.full(pdn.n, 300.0))
    meta = batch_meta(stack_problems([a, a]), NvpaxOptions())
    assert isinstance(meta, BatchMeta)
    hash(meta)  # jit static-arg requirement
    assert meta.n_depths == 4  # root + 3 internal levels
    assert meta.levels == (1,)


def test_controller_step_batched(pdn):
    """what-if API: K scenarios in one call, no controller state advance."""
    from repro.power.controller import PowerController

    ctl = PowerController(pdn)
    rng = np.random.default_rng(4)
    tele = rng.uniform(100, 600, (4, pdn.n))
    res = ctl.step_batched(tele)
    assert res.allocation.shape == (4, pdn.n)
    assert len(ctl.history) == 0  # what-if does not commit
    for k in range(4):
        assert _tree_feasible(pdn, res.allocation[k])
    # matches committing each scenario individually
    for k in range(4):
        res_s = ctl.step(tele[k])
        ctl._warm = None  # isolate scenarios (warm start biases nothing, but
        # keep the comparison strictly cold like the batched path)
        np.testing.assert_allclose(res.allocation[k], res_s.allocation, atol=ATOL)
