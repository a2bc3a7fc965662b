"""Phase-level mechanics: repair projection, saturation detection, and the
waterfill <-> iterated-LP equivalence."""

from __future__ import annotations

import functools
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import phases, solver
from repro.core.nvpax import NvpaxOptions, optimize
from repro.core.problem import AllocProblem
from repro.core.treeops import TreeTopo
from repro.core.waterfill import (
    waterfill,
    waterfill_arrays,
    waterfill_jax,
    waterfill_project_jax,
)
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
    res = optimize(ap, NvpaxOptions(run_phase2=False, run_phase3=False))
    assert res.stats["solves"] == 3  # one QP per distinct priority level
    assert res.stats["converged"]


def test_maxmin_phase_invariant_opt_plus_fixed():
    """Algorithm 2 line 7: A u F stays invariant as devices saturate."""
    pdn = build_from_level_sizes([2, 2], gpus_per_server=4)
    req = np.full(pdn.n, 300.0)
    ap = AllocProblem.build(pdn, req, active=np.ones(pdn.n, bool))
    res = optimize(ap, NvpaxOptions(use_waterfill=False, run_phase3=False))
    assert res.stats["converged"]
    assert (res.phase2 >= res.phase1 - 1e-9).all()


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


def _topo(start, end, cap, depth):
    return TreeTopo(
        jnp.asarray(start, jnp.int32),
        jnp.asarray(end, jnp.int32),
        jnp.asarray(cap, jnp.float64),
        jnp.asarray(depth, jnp.int32),
    )


def _uniform_case(depth, seed):
    """A uniform tree ``depth`` node levels deep (root included) with random
    fan-outs and a random raised set; each of the root's children has a
    heat of its own, so a hot subtree binds below the root's water level."""
    rng = np.random.default_rng(seed)
    pdn = build_from_level_sizes(
        list(rng.integers(2, 4, depth - 1)), gpus_per_server=int(rng.integers(2, 6))
    )
    tops = pdn.node_start[pdn.node_depth == 1]
    top = np.searchsorted(tops, np.arange(pdn.n), "right") - 1
    heat = rng.uniform(0.0, 1.0, tops.size)[top]
    base = pdn.dev_l + heat * rng.uniform(0.5, 0.95, pdn.n) * (pdn.dev_u - pdn.dev_l)
    opt = rng.random(pdn.n) < 0.7
    tree = (pdn.node_start, pdn.node_end, pdn.node_cap, pdn.node_depth)
    return tree, pdn.dev_u, base[None], opt[None]


def _fill_case(name):
    """(tree arrays, u, bases [L, n], raised sets [L, n]) of one case; L > 1
    runs the lanes under one ``vmap``."""
    if name == "two_rack":
        start, end, cap, u = _two_rack_tree()
        tree = (start, end, cap, [0, 1, 1])
        return tree, u, np.zeros((1, 4)), np.ones((1, 4), bool)
    if name == "hall_rounding":
        # as test_waterfill_freezes_binding_node_despite_rounding: 5e7 W bases
        start, end = np.array([0, 0, 2]), np.array([4, 2, 4])
        base = np.array([50000000.22715759, 50000000.62318715, 100.0, 100.0])
        cap_a = 100000001.69049819
        cap = np.array([cap_a + 1000.0, cap_a, 1e9])
        tree = (start, end, cap, [0, 1, 1])
        return tree, np.full(4, 1e9), base[None], np.ones((1, 4), bool)
    if name == "over_cap_at_entry":
        # rack A's bases already exceed its cap; rack B fills to the root's
        start, end = np.array([0, 0, 3]), np.array([6, 3, 6])
        cap = np.array([2600.0, 900.0, 2000.0])
        base = np.array([400.0, 300.0, 300.0, 200.0, 200.0, 200.0])
        tree = (start, end, cap, [0, 1, 1])
        return tree, np.full(6, 700.0), base[None], np.ones((1, 6), bool)
    if name == "mixed_u":
        pdn = build_from_level_sizes([2, 3], gpus_per_server=4)
        rng = np.random.default_rng(11)
        u = rng.uniform(300.0, 900.0, pdn.n)
        base = rng.uniform(150.0, 250.0, pdn.n)
        tree = (pdn.node_start, pdn.node_end, pdn.node_cap, pdn.node_depth)
        return tree, u, base[None], np.ones((1, pdn.n), bool)
    if name == "partial_mask":
        pdn = build_from_level_sizes([2, 2, 2], gpus_per_server=4)
        rng = np.random.default_rng(12)
        base = rng.uniform(200.0, 600.0, pdn.n)
        opt = np.zeros(pdn.n, bool)
        opt[::3] = True
        tree = (pdn.node_start, pdn.node_end, pdn.node_cap, pdn.node_depth)
        return tree, pdn.dev_u, base[None], opt[None]
    if name.startswith("uniform"):
        _, depth, seed = name.split("-")
        return _uniform_case(int(depth[1:]), int(seed[1:]))
    assert name == "vmap_lanes"
    pdn = build_from_level_sizes([3, 2, 2], gpus_per_server=4)
    rng = np.random.default_rng(13)
    bases = rng.uniform(200.0, 650.0, (4, pdn.n))
    bases[1] = pdn.dev_l  # an idle placement, every device at its floor
    opts = rng.random((4, pdn.n)) < np.array([[0.9], [1.0], [0.5], [0.2]])
    tree = (pdn.node_start, pdn.node_end, pdn.node_cap, pdn.node_depth)
    return tree, pdn.dev_u, bases, opts


@pytest.mark.parametrize(
    "case",
    [
        "two_rack",
        "hall_rounding",
        "over_cap_at_entry",
        "mixed_u",
        "partial_mask",
        "uniform-d2-s0",
        "uniform-d3-s1",
        "uniform-d4-s2",
        "uniform-d3-s3",
        "uniform-d4-s4",
        "vmap_lanes",
    ],
)
def test_waterfill_project_matches_the_sweep(case):
    """The level-wise tree projection gives the numpy sweep's allocation to
    1e-6 W, and adds nothing above any cap: each subtree sum stays at or
    below its cap, or at its bases' sum where that was over the cap already."""
    (start, end, cap, depth), u, bases, opts = _fill_case(case)
    start, end, cap, u = map(np.asarray, (start, end, cap, u))
    tree = _topo(start, end, cap, depth)
    n_depths = int(np.max(depth)) + 1

    def fill(b, o):
        return waterfill_project_jax(b, o, tree, jnp.asarray(u), n_depths)

    xs, steps, levels = jax.jit(jax.vmap(fill))(jnp.asarray(bases), jnp.asarray(opts))
    for x, base, opt in zip(np.asarray(xs), bases, opts):
        want = waterfill_arrays(start, end, cap, u, base, opt)
        np.testing.assert_allclose(x, want, rtol=0, atol=1e-6)
        np.testing.assert_array_equal(x[~opt], base[~opt])
        sums = [math.fsum(x[a:b]) for a, b in zip(start, end)]
        base_sums = [math.fsum(base[a:b]) for a, b in zip(start, end)]
        assert (np.array(sums) <= np.maximum(cap, base_sums) + 1e-6).all()
    assert (np.asarray(levels) <= n_depths).all()
    assert ((np.asarray(steps) > 0) == (np.asarray(levels) > 0)).all()


def _engine_step_and_direct_fills(pdn):
    """One engine step, and the (steps, levels) of a direct fill from each
    max-min phase's start: Phase II raises the active devices from the
    Phase I caps, Phase III the idle ones from the Phase II caps."""
    from repro.core.engine import AllocEngine

    eng = AllocEngine(pdn)
    tele = np.random.default_rng(5).uniform(50.0, 800.0, pdn.n)
    res = eng.step(tele)
    active = jnp.asarray(tele >= eng.idle_threshold)
    tree, u, nd = eng.fleet.tree, eng.fleet.u, eng.meta.n_depths
    _, s2, l2 = waterfill_project_jax(jnp.asarray(res.phase1), active, tree, u, nd)
    _, s3, l3 = waterfill_project_jax(jnp.asarray(res.phase2), ~active, tree, u, nd)
    assert eng.history[-1]["waterfill_rounds"] == res.stats["waterfill_rounds"]
    assert eng.history[-1]["waterfill_levels"] == res.stats["waterfill_levels"]
    return res.stats, [int(s2), int(s3)], [int(l2), int(l3)], nd


def test_engine_reports_the_waterfill_rounds_of_phases_2_and_3(small_pdn):
    """``stats["waterfill_rounds"]`` (search steps) and
    ``stats["waterfill_levels"]`` are what a direct fill from each phase's
    start counts."""
    stats, steps, levels, nd = _engine_step_and_direct_fills(small_pdn)
    assert stats["waterfill_rounds"] == steps
    assert stats["waterfill_levels"] == levels
    assert steps[0] > 0 and 1 <= levels[0] <= nd


def test_engine_reports_no_waterfill_search_where_no_node_binds():
    """With every cap the sum of its children's (oversubscription 1) no node
    can bind: no level is searched and no search step runs."""
    pdn = build_from_level_sizes([2, 3, 2], gpus_per_server=4, oversubscription=1.0)
    stats, steps, levels, _ = _engine_step_and_direct_fills(pdn)
    assert stats["waterfill_rounds"] == steps == [0, 0]
    assert stats["waterfill_levels"] == levels == [0, 0]


def _phase1_lanes(case):
    """(problems, pin_free) of one Phase I case: problems share one tree;
    more than one runs the lanes under one ``vmap``."""
    from repro.pdn.tenants import assign_tenants

    pdn = build_from_level_sizes([2, 3, 2], gpus_per_server=4)
    rng = np.random.default_rng(21)
    req = rng.uniform(400.0, 800.0, pdn.n)  # hot: the halls' caps bind
    dtype = jnp.float32 if case == "float32" else jnp.float64
    if case == "vmap_lanes":
        # rack caps 4,760 W (binding above 595 W a device), hall caps
        # 12,138 W (505.75 W), root 20,634.6 W (429.9 W): lanes bind at no
        # level, the root, one rack, and the halls and the root
        reqs = np.full((4, pdn.n), 240.0) + rng.uniform(-15.0, 15.0, (4, pdn.n))
        reqs[1] += 210.0
        reqs[2, :8] = 700.0
        reqs[3] += 320.0
        return [AllocProblem.build(pdn, r) for r in reqs], True
    if case == "three_levels":
        prio = assign_tenants(pdn, n_tenants=6, devices_per_tenant=8, seed=3).priority
        return [AllocProblem.build(pdn, req, priority=prio, normalized=True)], True
    ap = AllocProblem.build(pdn, req, normalized=case != "uniform_w", dtype=dtype)
    if case == "inv_u_w":
        u = rng.uniform(400.0, 900.0, pdn.n)
        ap = ap._replace(
            u=jnp.asarray(u), r=jnp.minimum(ap.r, u), weight_scale=jnp.asarray(1 / u)
        )
    if case in ("idle", "eps_l"):
        active = rng.random(pdn.n) < 0.7
        ap = AllocProblem.build(pdn, req + 150.0, active=active, normalized=True)
    if case == "over_cap_at_entry":
        # the first rack's cap below its eight devices' 1,600 W of floors
        rack = int(np.flatnonzero(pdn.node_end - pdn.node_start == 8)[0])
        ap = ap._replace(tree=ap.tree._replace(cap=ap.tree.cap.at[rack].set(1500.0)))
    return [ap], case != "eps_l"


def _active_set_qp(prob, tree, sla):
    """A level QP solved by SLSQP, an active-set method, over
    ``refsolve.dense_constraints``, then polished on its active set: it
    lands on the active bounds, where ``ref_solve``'s interior point stops
    inside them."""
    import scipy.optimize as sopt

    from repro.core.refsolve import dense_constraints

    n = prob.n
    w, t, lo, hi = (
        np.asarray(a, np.float64) for a in (prob.w, prob.target, prob.lo, prob.hi)
    )
    w = w * (1e-2 / np.max(w))  # the scale at which SLSQP's line search ends cleanly
    rows, _, row_hi = dense_constraints(tree, sla, n)
    rows, keep = rows[:, :n], np.isfinite(row_hi)
    res = sopt.minimize(
        lambda v: 0.5 * np.sum(w * (v - t) ** 2),
        x0=np.clip(t, lo, hi),
        jac=lambda v: w * (v - t),
        bounds=list(zip(lo, hi)),
        constraints=[{
            "type": "ineq",
            "fun": lambda v: row_hi[keep] - rows[keep] @ v,
            "jac": lambda v: -rows[keep],
        }],
        method="SLSQP",
        options={"ftol": 1e-15, "maxiter": 1000},
    )
    assert res.success, res.message
    # SLSQP stops on its objective, up to ~1e-4 W short in x: solve the
    # KKT equations of the active set it found, devices at their bounds
    # and caps held tight
    x = res.x
    at_lo, at_hi = x <= lo + 1e-7, x >= hi - 1e-7
    free = (w > 0) & ~at_lo & ~at_hi
    x = np.where(at_lo, lo, np.where(at_hi, hi, x))
    tight = rows[keep] @ x >= row_hi[keep] - 1e-6
    a = rows[keep][tight]
    af = a[:, free]
    mu = np.linalg.lstsq(
        (af / w[free]) @ af.T,
        af @ t[free] + a[:, ~free] @ x[~free] - row_hi[keep][tight],
        rcond=None,
    )[0]
    x[free] = t[free] - af.T @ mu / w[free]
    np.testing.assert_allclose(x, res.x, rtol=0, atol=1e-3)
    assert (x >= lo).all() and (x <= hi).all()
    return x


def _phase1_reference(ap, pin_free):
    """Phase I as one level QP per priority level, each solved by an
    active-set QP whose objective is checked to be no worse than
    ``refsolve.ref_solve``'s (whose interior point stops short of the
    active bounds, by up to tens of watts on these problems); a
    node whose devices' floors exceed its cap holds them at their floors
    (the cap is then left to the repair).  Returns the Phase I point and
    the devices so held."""
    from repro.core.refsolve import ref_solve

    f64 = functools.partial(jnp.asarray, dtype=jnp.float64)
    start, end = np.asarray(ap.tree.start), np.asarray(ap.tree.end)
    floors = np.array([math.fsum(np.asarray(ap.l)[a:b]) for a, b in zip(start, end)])
    over = floors > np.asarray(ap.tree.cap)
    held = np.zeros(ap.n, bool)
    for j in np.flatnonzero(over):
        held[start[j] : end[j]] = True
    cap = np.where(over, np.inf, np.asarray(ap.tree.cap, np.float64))
    tree = ap.tree._replace(cap=f64(cap))
    x = np.asarray(ap.l, np.float64)
    done = np.zeros(ap.n, bool)
    for p in ap.priority_levels():
        mask_a = np.asarray(ap.active & (ap.priority == p))
        prob = phases.qp_step(
            ap, jnp.asarray(x, ap.l.dtype), jnp.asarray(mask_a), jnp.asarray(done),
            1e-5, pin_free=pin_free,
        )
        prob = jax.tree_util.tree_map(f64, prob)
        prob = prob._replace(hi=jnp.where(held, prob.lo, prob.hi))
        x = _active_set_qp(prob, tree, ap.sla)
        # at least as good as the interior point's answer
        w, t = np.asarray(prob.w), np.asarray(prob.target)
        z = ref_solve(prob, tree, ap.sla)[: ap.n]
        assert np.sum(w * (x - t) ** 2) <= np.sum(w * (z - t) ** 2) * (1 + 1e-9)
        done |= mask_a
    return x, held


@pytest.mark.parametrize(
    "case",
    [
        "uniform_w",
        "inv_u_w",
        "three_levels",
        "eps_l",
        "over_cap_at_entry",
        "idle",
        "float32",
        "vmap_lanes",
    ],
)
def test_phase1_projection_matches_the_reference(case):
    """On problems with no tenant rows, Phase I through the jitted scan and
    through ``optimize`` is the exact projection: each equals
    reference's level QPs to 1e-6 W (1e-4 W in float32), runs no PDHG
    iteration, and searches exactly where a node binds."""
    from repro.core.batched import _phase1_scan, batch_meta, stack_problems
    from repro.core.solver import SolverState

    aps, pin_free = _phase1_lanes(case)
    stacked = stack_problems(aps)
    meta = batch_meta(stacked, NvpaxOptions())._replace(pin_free=pin_free)
    n, m = stacked.n, stacked.tree.m
    dtype = stacked.l.dtype
    atol = 1e-4 if dtype == jnp.float32 else 1e-6

    def scan(l, u, r, priority, active, weight_scale):
        ap = stacked._replace(
            l=l, u=u, r=r, priority=priority, active=active, weight_scale=weight_scale
        )
        warm = SolverState.zeros(n, m, 0, dtype)
        st = _phase1_scan(ap, meta, solver.SolverOptions(), warm)
        return st.x, st.iterations, st.search_steps, st.search_levels

    lanes = (stacked.l, stacked.u, stacked.r, stacked.priority, stacked.active,
             stacked.weight_scale)
    xs, iters, steps, levels = jax.jit(jax.vmap(scan))(*lanes)
    assert (np.asarray(iters) == 0).all()
    assert ((np.asarray(steps) > 0) == (np.asarray(levels) > 0)).all()
    for ap, x, lv in zip(aps, np.asarray(xs), np.asarray(levels)):
        want, held = _phase1_reference(ap, pin_free)
        np.testing.assert_allclose(x, want, rtol=0, atol=atol)
        np.testing.assert_array_equal(x[held], np.asarray(ap.l)[held])
        if pin_free:
            one = optimize(ap, NvpaxOptions(run_phase2=False, run_phase3=False))
            np.testing.assert_allclose(one.phase1, x, rtol=0, atol=1e-9)
            assert one.stats["phase_iterations"][0] == 0
            assert one.stats["solves"] == len(ap.priority_levels())
        # a level is searched exactly where its nodes bind at the requests
        x0 = np.clip(np.asarray(ap.r), np.asarray(ap.l), np.asarray(ap.u))
        x0 = np.where(np.asarray(ap.active), x0, np.asarray(ap.l))
        sums = np.array([math.fsum(x0[a:b]) for a, b in zip(
            np.asarray(ap.tree.start), np.asarray(ap.tree.end))])
        if case != "over_cap_at_entry":
            assert (lv > 0) == bool((sums > np.asarray(ap.tree.cap) + 1e-6).any())
    if case == "vmap_lanes":
        assert list(np.asarray(levels)) == [0, 1, 1, 2]


@pytest.mark.parametrize("oversubscription, binds", [(0.85, True), (1.0, False)])
def test_step_reports_the_projection_steps_of_phase_1(oversubscription, binds):
    """``stats["project_steps_p1"]`` and ``["project_levels_p1"]`` read 0
    where no node binds (every cap the sum of its children's) and are
    positive where one does, per lane on the batched path, with no PDHG
    iteration in Phase I."""
    from repro.core.batched import optimize_batched
    from repro.core.engine import AllocEngine

    pdn = build_from_level_sizes(
        [2, 3, 2], gpus_per_server=4, oversubscription=oversubscription
    )
    # busy devices: at oversubscription 0.85 the halls' caps bind (above
    # 505.75 W a device); then every device idle, with nothing to project
    tele = np.random.default_rng(5).uniform(500.0, 800.0, (2, pdn.n))
    tele[1] = 100.0
    eng = AllocEngine(pdn)
    res = eng.step(tele[0])
    steps, levels = res.stats["project_steps_p1"], res.stats["project_levels_p1"]
    assert (steps > 0, levels > 0) == (binds, binds)
    assert res.stats["phase_iterations"][0] == 0
    assert eng.history[-1]["project_steps_p1"] == steps
    assert eng.history[-1]["project_levels_p1"] == levels
    bres = optimize_batched([AllocProblem.build(pdn, t) for t in tele])
    np.testing.assert_array_equal(bres.stats["project_steps_p1"], [steps, 0])
    np.testing.assert_array_equal(bres.stats["project_levels_p1"], [levels, 0])


def test_phase1_keeps_pdhg_on_problems_with_tenant_rows():
    """With tenant rows Phase I runs the PDHG program it ran before the
    projection existed: the same iterations and the same caps as the
    parent commit's (200 iterations a lane; the Phase I sums below), and
    no projection step."""
    from repro.core.batched import optimize_batched
    from repro.pdn.tenants import assign_tenants

    pdn = build_from_level_sizes([2, 3, 2], gpus_per_server=4)
    layout = assign_tenants(pdn, n_tenants=4, devices_per_tenant=8, seed=1)
    reqs = np.random.default_rng(1).uniform(100, 650, (3, pdn.n))
    aps = [
        AllocProblem.build(pdn, r, sla=layout.sla_topo(), priority=layout.priority)
        for r in reqs
    ]
    res = optimize_batched(aps)
    np.testing.assert_array_equal(res.stats["phase_iterations"][:, 0], [200] * 3)
    np.testing.assert_allclose(
        res.phase1.sum(axis=1),
        [18883.667708152192, 18691.41086058721, 18091.801983496116],
        rtol=0,
        atol=1e-6,
    )
    assert (res.stats["project_steps_p1"] == 0).all()
    one = optimize(aps[0], NvpaxOptions(run_phase2=False, run_phase3=False))
    assert one.stats["phase_iterations"][0] == 200
    np.testing.assert_allclose(one.phase1, res.phase1[0], rtol=0, atol=1e-9)
