"""Phase-level mechanics: repair projection, saturation detection, and the
waterfill <-> iterated-LP equivalence."""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import pdhg, phases
from repro.core.nvpax import NvpaxOptions, optimize
from repro.core.problem import AllocProblem
from repro.core.treeops import TreeTopo
from repro.core.waterfill import waterfill, waterfill_arrays, waterfill_jax
from repro.pdn.tree import build_from_level_sizes

pytestmark = pytest.mark.usefixtures("x64")


def test_repair_restores_feasibility(small_pdn):
    req = np.random.default_rng(0).uniform(100, 700, small_pdn.n)
    ap = AllocProblem.build(small_pdn, req)
    # deliberately violate: everyone at u
    x_bad = jnp.asarray(small_pdn.dev_u)
    x = np.asarray(phases.repair(x_bad, ap))
    csum = np.concatenate([[0.0], np.cumsum(x)])
    sums = csum[small_pdn.node_end] - csum[small_pdn.node_start]
    assert (sums <= small_pdn.node_cap + 1e-9).all()
    assert (x >= small_pdn.dev_l - 1e-12).all()
    assert (x <= small_pdn.dev_u + 1e-12).all()


def test_repair_noop_when_feasible(small_pdn):
    req = np.random.default_rng(1).uniform(100, 700, small_pdn.n)
    ap = AllocProblem.build(small_pdn, req)
    x = jnp.asarray(small_pdn.dev_l)  # minimums always feasible
    np.testing.assert_allclose(np.asarray(phases.repair(x, ap)), small_pdn.dev_l)


def test_saturated_mask_detects_box_and_tree(tiny_pdn):
    req = np.full(tiny_pdn.n, 500.0)
    ap = AllocProblem.build(tiny_pdn, req)
    # device 0 at its upper bound -> saturated via box
    x = jnp.asarray(np.concatenate([[700.0], np.full(7, 200.0)]))
    sat = np.asarray(phases.saturated_mask(x, ap, jnp.ones(8, bool)))
    assert sat[0] and not sat[1:].any()
    # fill server 0 (cap 2400) exactly -> its 4 devices saturated
    x = jnp.asarray(np.concatenate([np.full(4, 600.0), np.full(4, 200.0)]))
    sat = np.asarray(phases.saturated_mask(x, ap, jnp.ones(8, bool)))
    assert sat[:4].all() and not sat[4:].any()


def test_waterfill_equals_lp_path(small_pdn):
    """The exact water-filling fast path and the paper's iterated max-min LP
    converge to the same allocation (lexicographic max-min)."""
    rng = np.random.default_rng(3)
    req = rng.uniform(100, 500, small_pdn.n)
    ap = AllocProblem.build(small_pdn, req)
    res_wf = optimize(ap, NvpaxOptions(use_waterfill=True))
    res_lp = optimize(ap, NvpaxOptions(use_waterfill=False))
    assert res_lp.stats["converged"]
    np.testing.assert_allclose(res_wf.allocation, res_lp.allocation, atol=0.01)


def test_waterfill_maxmin_property(small_pdn):
    """No feasible transfer can raise the minimum raise: every non-maximal
    device is blocked by a tight node or its own bound."""
    base = small_pdn.dev_l.copy()
    mask = np.ones(small_pdn.n, bool)
    x = waterfill(small_pdn, base, mask)
    csum = np.concatenate([[0.0], np.cumsum(x)])
    sums = csum[small_pdn.node_end] - csum[small_pdn.node_start]
    slack = small_pdn.node_cap - sums
    tight_nodes = slack <= 1e-6
    under_tight = np.zeros(small_pdn.n, bool)
    for j in np.nonzero(tight_nodes)[0]:
        under_tight[small_pdn.node_start[j] : small_pdn.node_end[j]] = True
    at_u = x >= small_pdn.dev_u - 1e-6
    assert (under_tight | at_u).all()


def test_waterfill_respects_frozen_devices(small_pdn):
    base = small_pdn.dev_l.copy()
    mask = np.ones(small_pdn.n, bool)
    mask[::2] = False  # freeze half
    x = waterfill(small_pdn, base, mask)
    np.testing.assert_array_equal(x[::2], base[::2])
    assert (x[1::2] > base[1::2]).any()


@pytest.mark.parametrize("impl", ["numpy", "jax"])
def test_waterfill_freezes_binding_node_despite_rounding(impl):
    """The node whose rate set the raise freezes even when its recomputed
    subtree sum rounds more than 1e-9 W below its cap.  At 1e8 W one ulp is
    1.5e-8 W; float64 as emulated on a TPU rounds that coarsely at hall
    scale.  A round that froze nothing used to end the sweep early, here
    with node B's devices left near their base instead of filled to 500 W."""
    # root [0, 4) over A = [0, 2) and B = [2, 4); A binds first
    start, end = np.array([0, 0, 2]), np.array([4, 2, 4])
    base = np.array([50000000.22715759, 50000000.62318715, 100.0, 100.0])
    cap_a = 100000001.69049819
    cap = np.array([cap_a + 1000.0, cap_a, 1e9])
    u = np.full(4, 1e9)
    opt = np.ones(4, bool)
    if impl == "numpy":
        x = waterfill_arrays(start, end, cap, u, base, opt)
    else:
        tree = TreeTopo(
            jnp.asarray(start, jnp.int32),
            jnp.asarray(end, jnp.int32),
            jnp.asarray(cap),
            jnp.asarray([0, 1, 1], jnp.int32),
        )
        x = np.asarray(
            waterfill_jax(jnp.asarray(base), jnp.asarray(opt), tree, jnp.asarray(u))[0]
        )
    np.testing.assert_allclose(x[2:], 500.0, atol=1e-6)


def test_phase1_processes_priorities_high_to_low():
    pdn = build_from_level_sizes([2], gpus_per_server=4)  # 8 devices
    req = np.full(8, 600.0)
    prio = np.array([1, 1, 2, 2, 3, 3, 1, 1], np.int32)
    ap = AllocProblem.build(pdn, req, active=np.ones(8, bool), priority=prio)
    x, state, stats = phases.phase1(ap, pdhg.SolverOptions())
    assert stats.solves == 3  # one QP per distinct priority level
    assert stats.converged


def test_maxmin_phase_invariant_opt_plus_fixed():
    """Algorithm 2 line 7: A u F stays invariant as devices saturate."""
    pdn = build_from_level_sizes([2, 2], gpus_per_server=4)
    req = np.full(pdn.n, 300.0)
    ap = AllocProblem.build(pdn, req, active=np.ones(pdn.n, bool))
    x1, state, _ = phases.phase1(ap, pdhg.SolverOptions())
    x2, _, st2 = phases.run_maxmin_phase(
        ap, x1, ap.active, ap.idle, pdhg.SolverOptions(), use_waterfill=False
    )
    assert st2.converged
    assert (np.asarray(x2) >= np.asarray(x1) - 1e-9).all()


def _two_rack_tree():
    """Root [0, 4) over rack A = [0, 2) (cap 100 W) and rack B = [2, 4) (cap
    500 W); device 2 tops out at 150 W."""
    start, end = np.array([0, 0, 2]), np.array([4, 2, 4])
    cap = np.array([1e9, 100.0, 500.0])
    u = np.array([1e9, 1e9, 150.0, 1e9])
    return start, end, cap, u


def test_waterfill_jax_counts_one_round_per_binding_event():
    """From zero: A binds at 50 W each (round 1), device 2 reaches its u at
    150 W (round 2), B binds with device 3 at 350 W (round 3); nothing is
    left live, so the sweep stops after three rounds."""
    start, end, cap, u = _two_rack_tree()
    tree = TreeTopo(
        jnp.asarray(start, jnp.int32),
        jnp.asarray(end, jnp.int32),
        jnp.asarray(cap),
        jnp.asarray([0, 1, 1], jnp.int32),
    )
    x, rounds = waterfill_jax(
        jnp.zeros(4), jnp.ones(4, bool), tree, jnp.asarray(u)
    )
    assert int(rounds) == 3
    np.testing.assert_allclose(np.asarray(x), [50.0, 50.0, 150.0, 350.0])
    np.testing.assert_allclose(
        np.asarray(x), waterfill_arrays(start, end, cap, u, np.zeros(4), np.ones(4, bool))
    )


def test_engine_reports_the_waterfill_rounds_of_phases_2_and_3(small_pdn):
    """``stats["waterfill_rounds"]`` is what a direct sweep from each phase's
    start counts: Phase II raises the active devices from the Phase I caps,
    Phase III the idle ones from the Phase II caps."""
    from repro.core.engine import AllocEngine

    eng = AllocEngine(small_pdn)
    tele = np.random.default_rng(5).uniform(50.0, 800.0, small_pdn.n)
    res = eng.step(tele)
    active = jnp.asarray(tele >= eng.idle_threshold)
    tree, u = eng.fleet.tree, eng.fleet.u
    _, r2 = waterfill_jax(jnp.asarray(res.phase1), active, tree, u)
    _, r3 = waterfill_jax(jnp.asarray(res.phase2), ~active, tree, u)
    assert res.stats["waterfill_rounds"] == [int(r2), int(r3)]
    assert int(r2) > 0
    assert eng.history[-1]["waterfill_rounds"] == res.stats["waterfill_rounds"]
