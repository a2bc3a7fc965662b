"""Per-phase caps of the fleet step, in every dispatch mode.

A campus of identical halls under a feed that carries the sum of the hall
feeds (the ``fleet98k`` benchmark cell's tree, with fewer nodes per level):
the tree is cut below the campus feed, one domain per hall.  In this regime
the default ``"waterfill"`` coordinator grants every hall its own feed, and
the fleet's answer is the monolithic three-phase solve of the whole tree.
So ``FleetStepResult.phase1``/``phase2``/``allocation`` must match

* the monolithic ``AllocEngine`` on the uncut tree, to 1e-6 W;
* the benchmark's plain numpy reference (``benchmarks/chip/reference.py``,
  loaded by path), within the cell's limits;

and keep every subtree's exact (``fsum``) sum within its cap.  An
incremental step that skips every domain returns the phases of a full
solve of the same inputs.
"""

from __future__ import annotations

import importlib.util
import json
import math
from pathlib import Path

import numpy as np
import pytest

from repro.core.engine import AllocEngine
from repro.core.nvpax import NvpaxOptions
from repro.core.solver import SolverOptions
from repro.fleet import FleetOrchestrator
from repro.pdn.tree import PDNNode, flatten

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
FANOUT = [4, 2, 3, 4, 4]  # campus -> halls -> rows -> racks -> servers of 4
OVERSUB = [1.0, 0.85, 0.85, 0.85, 1.0]
L, U, IDLE = 200.0, 700.0, 150.0
MODES = ["stacked", "loop", "sharded"]


def _load(name):
    spec = importlib.util.spec_from_file_location(f"chip_{name}", CHIP / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load("reference")
LIMITS = json.loads((CHIP / "configs" / "fleet98k.json").read_text())["limits"]


def _campus():
    def node(d):
        if d == len(FANOUT) - 1:
            return PDNNode(capacity=OVERSUB[d] * FANOUT[d] * U, n_devices=FANOUT[d])
        kids = [node(d + 1) for _ in range(FANOUT[d])]
        n = PDNNode(capacity=OVERSUB[d] * FANOUT[d] * kids[0].capacity)
        n.children = kids
        return n

    return flatten(node(0), default_l=L, default_u=U)


PDN = _campus()
TREE = reference.Tree(FANOUT, OVERSUB, L, U)
HALL = PDN.n // FANOUT[0]


def _telemetry(seed):
    """Hall 0 runs hot (its feed binds Phase I), hall 1 holds a block of
    idle devices (Phase III raises them), the rest is mixed."""
    rng = np.random.default_rng(seed)
    tele = rng.uniform(60.0, 700.0, PDN.n)
    tele[:HALL] = rng.uniform(640.0, 700.0, HALL)
    tele[HALL : HALL + HALL // 3] = rng.uniform(40.0, 140.0, HALL // 3)
    return tele


TELES = [_telemetry(s) for s in (3, 4, 5)]  # cold + two warm-carried steps
# where a hall's feed binds, Phase I is a PDHG solve: both sides solve it
# well below the 1e-6 W bar they are compared at
TIGHT = SolverOptions(eps_abs=1e-11, eps_rel=1e-11, max_iters=20_000)


@pytest.fixture(scope="module")
def monolithic():
    eng = AllocEngine(PDN, options=NvpaxOptions(solver=TIGHT))
    return [eng.step(t) for t in TELES]


@pytest.fixture(scope="module", params=MODES)
def fleet(request):
    orch = FleetOrchestrator(
        PDN, level=1, mode=request.param, options=NvpaxOptions(solver=TIGHT)
    )
    assert orch.mode == request.param and orch.k == FANOUT[0]
    return orch, [orch.step(t) for t in TELES]


def _phases(res):
    return res.phase1, res.phase2, res.allocation


def test_fleet_phases_match_the_monolithic_engine(fleet, monolithic):
    _, results = fleet
    for rf, rm in zip(results, monolithic):
        for got, want in zip(_phases(rf), _phases(rm)):
            assert got.shape == (PDN.n,)
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
        # the regime the fixture means to exercise: the hot hall's feed
        # binds Phase I, the idle block is raised in Phase III
        assert abs(math.fsum(rf.phase1[:HALL]) - TREE.cap[1]) <= 1e-6
        assert np.max(rf.allocation - rf.phase2) > 1.0


def test_waterfill_grants_are_the_hall_feeds_when_the_campus_feed_is_ample(fleet):
    orch, results = fleet
    assert orch.coordinator.mode == "waterfill"
    for res in results:
        np.testing.assert_allclose(res.grants, TREE.cap[1], rtol=0, atol=1e-6)


def test_fleet_phases_match_the_exact_reference(fleet):
    _, results = fleet
    for tele, res in zip(TELES, results):
        ref = reference.three_phase(TREE, tele, IDLE)
        for got, want in zip(_phases(res), ref):
            assert np.max(np.abs(got - want)) <= LIMITS["gap_w"]
            assert abs(math.fsum(got) - math.fsum(want)) <= LIMITS["total_gap_w"]


def test_fleet_phases_keep_every_subtree_within_its_cap(fleet):
    _, results = fleet
    for res in results:
        for x in _phases(res):
            assert np.all(x >= L - LIMITS["excess_w"])
            assert np.all(x <= U + LIMITS["excess_w"])
            for d, block in enumerate(TREE.block):
                sums = [math.fsum(row) for row in x.reshape(-1, block)]
                assert max(sums) <= TREE.cap[d] + LIMITS["excess_w"]


INC = NvpaxOptions(incremental=True, solver=TIGHT)


@pytest.mark.parametrize("mode", MODES)
def test_all_skip_step_returns_the_phases_of_a_full_solve(mode, monolithic):
    """The second of two identical steps skips every domain; it returns the
    anchor's Phase I and Phase II caps, not its final caps twice."""
    orch = FleetOrchestrator(PDN, level=1, mode=mode, options=INC)
    orch.step(TELES[0])
    res = orch.step(TELES[0])
    assert np.all(res.stats["skipped"])
    assert int(np.sum(res.stats["iterations"])) == 0
    full = monolithic[0]
    assert np.max(full.allocation - full.phase2) > 1.0  # the phases differ
    for got, want in zip(_phases(res), _phases(full)):
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
