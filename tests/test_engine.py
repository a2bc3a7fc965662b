"""Persistent allocation engine (:mod:`repro.core.engine`): zero-rebuild
steps match the rebuild-every-step path, warm-start carry semantics, and the
batched deadline/iteration-budget mode.

The engine runs the SAME traced driver as the batched path and
``optimize`` (``solve_three_phase``), so engine-served steps must match the
legacy ``PowerController.step`` (``AllocProblem.build`` + ``optimize``
every interval) to 1e-9 W — observed deviation is ~1e-12.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.batched import optimize_batched
from repro.core.engine import AllocEngine
from repro.core.nvpax import NvpaxOptions, optimize
from repro.core.solver import SolverOptions
from repro.core.problem import AllocProblem, FleetTopology
from repro.pdn.tenants import assign_tenants
from repro.pdn.tree import build_from_level_sizes
from repro.power.controller import ControllerConfig, PowerController

ATOL = 1e-9  # engine vs rebuild path: structurally identical programs


@pytest.fixture(scope="module")
def pdn():
    return build_from_level_sizes([2, 3, 2], gpus_per_server=4)  # n = 48


@pytest.fixture(scope="module")
def sla_fleet(pdn):
    layout = assign_tenants(pdn, n_tenants=4, devices_per_tenant=8, seed=1)
    return layout, layout.sla_topo()


def _tree_feasible(pdn, x, tol=1e-6):
    csum = np.concatenate([[0.0], np.cumsum(x)])
    sums = csum[pdn.node_end] - csum[pdn.node_start]
    return (sums <= pdn.node_cap + tol).all()


# ---------------------------------------------------------------------------
# engine == rebuild path
# ---------------------------------------------------------------------------


def test_engine_step_matches_rebuild_path(pdn):
    """Warm-carried engine steps match warm-carried build+optimize steps to
    1e-9 W on randomized telemetry."""
    rng = np.random.default_rng(0)
    eng = AllocEngine(pdn)
    warm = None
    for t in range(3):
        tele = rng.uniform(50, 650, pdn.n)
        res_e = eng.step(tele)
        res_h = optimize(AllocProblem.build(pdn, tele), warm=warm)
        warm = res_h.warm_state
        np.testing.assert_allclose(
            res_e.allocation, res_h.allocation, atol=ATOL,
            err_msg=f"step {t}",
        )
        assert res_e.stats["total_iterations"] == res_h.stats["total_iterations"]
        assert _tree_feasible(pdn, res_e.allocation)


def test_engine_step_matches_rebuild_path_sla(pdn, sla_fleet):
    """Same, on a tenant-SLA fleet with mixed priorities (iterated-LP
    max-min phases, multi-level Phase I sweep)."""
    layout, sla = sla_fleet
    rng = np.random.default_rng(1)
    eng = AllocEngine(pdn, sla=sla, priority=layout.priority)
    warm = None
    for t in range(2):
        tele = rng.uniform(100, 650, pdn.n)
        res_e = eng.step(tele)
        res_h = optimize(
            AllocProblem.build(pdn, tele, sla=sla, priority=layout.priority),
            warm=warm,
        )
        warm = res_h.warm_state
        np.testing.assert_allclose(
            res_e.allocation, res_h.allocation, atol=ATOL,
            err_msg=f"step {t}",
        )


def test_engine_pinned_levels_skip_empty(pdn):
    """The engine pins priority levels from the full layout at construction;
    a level with no active devices is skipped by the traced cond, matching
    ``optimize``'s active-only sweep without recompiling."""
    priority = np.where(np.arange(pdn.n) % 2 == 0, 2, 1).astype(np.int32)
    eng = AllocEngine(pdn, priority=priority)
    assert eng.meta.levels == (2, 1)
    rng = np.random.default_rng(2)
    tele = rng.uniform(200, 650, pdn.n)
    tele[priority == 2] = 50.0  # all priority-2 devices idle
    res_e = eng.step(tele)
    res_h = optimize(AllocProblem.build(pdn, tele, priority=priority))
    np.testing.assert_allclose(res_e.allocation, res_h.allocation, atol=ATOL)


# ---------------------------------------------------------------------------
# warm-start carry
# ---------------------------------------------------------------------------


def test_warm_carry_matches_cold_host_and_engine(pdn):
    """Warm-start is an optimization, not semantics: on tree-only fleets
    (unique optimum) warm-carried and cold steps agree tightly, host and
    engine paths alike."""
    rng = np.random.default_rng(3)
    tele0 = rng.uniform(100, 600, pdn.n)
    tele1 = np.clip(tele0 + rng.normal(0, 20, pdn.n), 60, 690)

    r0 = optimize(AllocProblem.build(pdn, tele0))
    ap1 = AllocProblem.build(pdn, tele1)
    cold = optimize(ap1)
    warm = optimize(ap1, warm=r0.warm_state)
    np.testing.assert_allclose(warm.allocation, cold.allocation, atol=1e-6)

    eng = AllocEngine(pdn)
    eng.step(tele0)
    warm_e = eng.step(tele1)  # warm-carried
    eng.reset_warm()
    cold_e = eng.step(tele1)
    np.testing.assert_allclose(warm_e.allocation, cold_e.allocation, atol=1e-6)


def test_warm_carry_equivalent_quality_sla(pdn, sla_fleet):
    """On SLA fleets the max-min LPs are degenerate (eps tie-breaking), so
    warm and cold may pick different equal-quality vertices: assert Phase I
    equality, feasibility, and identical total allocated power instead of
    per-device equality.  Runs at tight solver tolerance so both solves
    land machine-exact on the binding rows (at the default tolerance each
    certified exit may undershoot them by O(eps * fleet_power), which is
    solver tolerance, not a quality difference)."""
    layout, sla = sla_fleet
    opts = NvpaxOptions(solver=SolverOptions(eps_abs=1e-11, eps_rel=1e-11))
    rng = np.random.default_rng(4)
    tele0 = rng.uniform(100, 650, pdn.n)
    tele1 = tele0 * 1.01
    r0 = optimize(
        AllocProblem.build(pdn, tele0, sla=sla, priority=layout.priority), opts
    )
    ap1 = AllocProblem.build(pdn, tele1, sla=sla, priority=layout.priority)
    cold = optimize(ap1, opts)
    warm = optimize(ap1, opts, warm=r0.warm_state)
    assert warm.stats["converged"] and cold.stats["converged"]
    np.testing.assert_allclose(warm.phase1, cold.phase1, atol=1e-6)
    assert _tree_feasible(pdn, warm.allocation)
    assert abs(warm.allocation.sum() - cold.allocation.sum()) < 1e-3


def test_batched_warm_carry_reduces_iterations(pdn, sla_fleet):
    """Carrying the batched per-phase warm state across consecutive control
    steps reduces cumulative solver iterations on drifting telemetry.

    Cumulative over a short trace, not per-step: the solver-core overhaul
    made cold solves certify quickly, so a single-step comparison is
    instance noise (see test_host_warm_carry_reduces_iterations)."""
    layout, sla = sla_fleet
    rng = np.random.default_rng(5)
    tb = rng.uniform(100, 650, (3, pdn.n))

    eng = AllocEngine(pdn, sla=sla, priority=layout.priority)
    eng.step_batched(tb)  # primes the warm carry
    tot_warm = tot_cold = 0.0
    for _ in range(3):
        tb = np.clip(tb + rng.normal(0, 6, tb.shape), 80, 690)
        eng_cold = AllocEngine(pdn, sla=sla, priority=layout.priority)
        cold_res = eng_cold.step_batched(tb)
        warm_res = eng.step_batched(tb)
        tot_cold += cold_res.stats["iterations"].mean()
        tot_warm += warm_res.stats["iterations"].mean()
        # never catastrophically poisoned by the carried duals
        assert (
            warm_res.stats["iterations"].mean()
            <= 1.5 * cold_res.stats["iterations"].mean()
        )
        assert warm_res.stats["converged"].all()
        for k in range(3):
            assert _tree_feasible(pdn, warm_res.allocation[k])
    assert tot_warm <= tot_cold, (tot_warm, tot_cold)


def test_host_warm_carry_reduces_iterations(pdn, sla_fleet):
    """Host-path per-phase carry (phases.WarmCarry) cuts cumulative
    iterations on a drifting steady-state trace.

    Pre-overhaul this asserted a strict per-step win: cold solves were slow
    enough (certification stalls) that any warm start beat them.  The
    solver-core overhaul made cold solves certify quickly, so the per-step
    comparison is instance-noise — the carry's contract is the cumulative
    steady-state cost, with no step catastrophically poisoned."""
    layout, sla = sla_fleet
    rng = np.random.default_rng(6)
    tele = rng.uniform(100, 650, pdn.n)
    r = optimize(AllocProblem.build(pdn, tele, sla=sla, priority=layout.priority))
    warm_state = r.warm_state
    tot_warm = tot_cold = 0
    for _ in range(4):
        tele = np.clip(tele + rng.normal(0, 6, pdn.n), 80, 690)
        ap = AllocProblem.build(pdn, tele, sla=sla, priority=layout.priority)
        cold = optimize(ap)
        warm = optimize(ap, warm=warm_state)
        warm_state = warm.warm_state
        tot_cold += cold.stats["total_iterations"]
        tot_warm += warm.stats["total_iterations"]
        # no single step catastrophically poisoned by the carried duals
        assert (
            warm.stats["total_iterations"]
            <= 1.5 * cold.stats["total_iterations"]
        )
    assert tot_warm <= tot_cold


# ---------------------------------------------------------------------------
# deadline / iteration-budget mode
# ---------------------------------------------------------------------------


def test_batched_iter_budget_truncates_to_phase1(pdn, sla_fleet):
    """Budget 1: refinement phases are skipped, allocation == Phase I output
    (still feasible), stats['truncated'] set — a zero deadline's
    semantics."""
    layout, sla = sla_fleet
    rng = np.random.default_rng(7)
    aps = [
        AllocProblem.build(pdn, r, sla=sla, priority=layout.priority)
        for r in rng.uniform(100, 650, (2, pdn.n))
    ]
    res = optimize_batched(aps, iter_budget=1)
    assert res.stats["truncated"].all()
    np.testing.assert_allclose(res.allocation, res.phase1, atol=0)
    for k in range(2):
        assert _tree_feasible(pdn, res.allocation[k])


def test_batched_iter_budget_large_matches_unbudgeted(pdn, sla_fleet):
    layout, sla = sla_fleet
    rng = np.random.default_rng(8)
    aps = [
        AllocProblem.build(pdn, r, sla=sla, priority=layout.priority)
        for r in rng.uniform(100, 650, (2, pdn.n))
    ]
    full = optimize_batched(aps)
    budgeted = optimize_batched(aps, iter_budget=10**8)
    assert not budgeted.stats["truncated"].any()
    np.testing.assert_allclose(budgeted.allocation, full.allocation, atol=ATOL)


def test_batched_deadline_s_honored(pdn, sla_fleet):
    """options.deadline_s drives the calibrated iteration budget: a tiny
    deadline truncates, a generous one does not (was silently ignored)."""
    layout, sla = sla_fleet
    rng = np.random.default_rng(9)
    aps = [
        AllocProblem.build(pdn, r, sla=sla, priority=layout.priority)
        for r in rng.uniform(100, 650, (2, pdn.n))
    ]
    tiny = optimize_batched(aps, NvpaxOptions(deadline_s=1e-7))
    assert tiny.stats["truncated"].all()
    assert tiny.stats["iter_budget"] is not None
    roomy = optimize_batched(aps, NvpaxOptions(deadline_s=600.0))
    assert not roomy.stats["truncated"].any()


def test_engine_step_deadline(pdn, sla_fleet):
    layout, sla = sla_fleet
    rng = np.random.default_rng(10)
    eng = AllocEngine(pdn, sla=sla, priority=layout.priority)
    tele = rng.uniform(100, 650, pdn.n)
    res = eng.step(tele, deadline_s=1e-7)
    assert res.stats["truncated"]
    np.testing.assert_allclose(res.allocation, res.phase1, atol=0)
    res2 = eng.step(tele, deadline_s=600.0)
    assert not res2.stats["truncated"]


# ---------------------------------------------------------------------------
# FleetTopology build fast path
# ---------------------------------------------------------------------------


def test_build_with_prebuilt_topology_matches(pdn, sla_fleet):
    layout, sla = sla_fleet
    topo = FleetTopology.from_pdn(pdn, sla=sla)
    rng = np.random.default_rng(11)
    tele = rng.uniform(50, 650, pdn.n)
    a = AllocProblem.build(pdn, tele, sla=sla, priority=layout.priority)
    b = AllocProblem.build(pdn, tele, priority=layout.priority, topology=topo)
    for leaf in ("l", "u", "r", "priority", "active", "weight_scale"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, leaf)), np.asarray(getattr(b, leaf)), leaf
        )
    np.testing.assert_array_equal(np.asarray(a.tree.cap), np.asarray(b.tree.cap))
    np.testing.assert_array_equal(np.asarray(a.sla.hi), np.asarray(b.sla.hi))
    with pytest.raises(ValueError):
        AllocProblem.build(pdn, tele, sla=sla, topology=topo)


# ---------------------------------------------------------------------------
# controller on the engine
# ---------------------------------------------------------------------------


def test_controller_engine_matches_legacy(pdn):
    """Engine-served PowerController.step == legacy rebuild-every-step
    controller to 1e-9 W, including across a device-failure event."""
    ctl_e = PowerController(pdn, config=ControllerConfig(use_engine=True))
    ctl_l = PowerController(pdn, config=ControllerConfig(use_engine=False))
    rng = np.random.default_rng(12)
    for t in range(4):
        if t == 2:
            ctl_e.fail_devices([3, 17])
            ctl_l.fail_devices([3, 17])
        tele = rng.uniform(50, 650, pdn.n)
        res_e = ctl_e.step(tele)
        res_l = ctl_l.step(tele)
        np.testing.assert_allclose(
            res_e.allocation, res_l.allocation, atol=ATOL, err_msg=f"step {t}"
        )
    assert len(ctl_e.history) == len(ctl_l.history) == 4


def test_controller_step_batched_engine_path(pdn):
    """Engine-backed what-if: no history advance, feasible output, warm
    carried across calls of the same batch size."""
    ctl = PowerController(pdn)
    rng = np.random.default_rng(13)
    tele = rng.uniform(100, 600, (3, pdn.n))
    res = ctl.step_batched(tele)
    assert res.allocation.shape == (3, pdn.n)
    assert len(ctl.history) == 0
    assert 3 in ctl._engine._batched_warm  # warm carried for K=3
    res2 = ctl.step_batched(tele * 1.002)
    assert res2.stats["iterations"].mean() <= res.stats["iterations"].mean()
    for k in range(3):
        assert _tree_feasible(pdn, res2.allocation[k])


def test_what_if_is_stateless_and_deterministic(pdn, sla_fleet):
    """what_if never carries warm state: identical inputs -> identical
    outputs, even on SLA fleets where warm carry could pick a different
    equal-quality max-min vertex."""
    layout, sla = sla_fleet
    ctl = PowerController(pdn, sla=sla, priority=layout.priority)
    rng = np.random.default_rng(15)
    tele = rng.uniform(100, 650, (2, pdn.n))
    a = ctl.what_if(tele)
    assert not ctl._engine._batched_warm  # nothing stored
    b = ctl.what_if(tele)
    np.testing.assert_array_equal(a.allocation, b.allocation)


def test_controller_supply_scale_repins_engine_no_recompile(pdn):
    """A supply drop re-pins the existing engine's capacity arrays in place:
    same engine object, no retrace of the compiled step program, and the new
    caps are enforced from the next step (ISSUE 3 satellite)."""
    from repro.core.engine import trace_count

    ctl = PowerController(pdn)
    rng = np.random.default_rng(14)
    tele = rng.uniform(200, 650, pdn.n)
    ctl.step(tele)
    ctl.step(tele)  # compile both cold and warm-carry jit variants
    eng_before = ctl._engine
    traces_before = trace_count()
    ctl.set_supply_scale(0.8)
    res = ctl.step(tele)
    assert ctl._engine is eng_before  # re-pinned, not rebuilt
    assert trace_count() == traces_before  # caps are traced: no recompile
    csum = np.concatenate([[0.0], np.cumsum(res.allocation)])
    sums = csum[pdn.node_end] - csum[pdn.node_start]
    assert (sums <= 0.8 * pdn.node_cap + 1e-6).all()
    # scales are absolute vs construction caps, not compounding
    ctl.set_supply_scale(1.0)
    res2 = ctl.step(tele)
    np.testing.assert_allclose(
        np.asarray(ctl._engine.fleet.tree.cap), pdn.node_cap
    )
    assert res2.allocation.sum() >= res.allocation.sum() - 1e-6


def test_engine_reports_per_phase_iterations(pdn, sla_fleet):
    """ISSUE 3 satellite: per-phase PDHG iteration split in engine stats
    (groundwork for a per-phase deadline cost model).  On SLA fleets the
    max-min phases run the LP path, so all three phases report work; the
    split must sum to the total."""
    layout, sla = sla_fleet
    eng = AllocEngine(pdn, sla=sla, priority=layout.priority)
    res = eng.step(np.random.default_rng(21).uniform(200, 650, pdn.n))
    pi = res.stats["phase_iterations"]
    assert len(pi) == 3
    assert sum(pi) == res.stats["total_iterations"]
    assert pi[0] > 0 and pi[1] > 0  # QP sweep + Phase II LP both iterate
    # batched path reports the same split per scenario
    bres = eng.step_batched(
        np.random.default_rng(22).uniform(200, 650, (2, pdn.n))
    )
    assert bres.stats["iterations_per_phase"].shape == (2, 3)
    np.testing.assert_array_equal(
        bres.stats["iterations_per_phase"].sum(axis=1),
        bres.stats["iterations"],
    )


def test_set_root_cap_fast_path_validates(pdn):
    """set_root_cap skips the full repin revalidation (fleet hot path) but
    still rejects grants below the subtree minimum draw."""
    eng = AllocEngine(pdn)
    with pytest.raises(ValueError, match="device minimums"):
        eng.set_root_cap(10.0)
    eng.set_root_cap(0.5 * pdn.node_cap[0])
    assert float(np.asarray(eng.fleet.tree.cap)[0]) == 0.5 * pdn.node_cap[0]
    res = eng.step(np.full(pdn.n, 650.0))
    assert res.allocation.sum() <= 0.5 * pdn.node_cap[0] + 1e-6
