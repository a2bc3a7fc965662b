"""The benchmark's comparison, on the CPU at a size a test run holds.

The harness's look for a chip is skipped (``bench.run`` is called
directly); everything else of a run is driven: the deployment built from
the configuration, the mix's telemetry, warm-up, the timed loop and the
comparison with the plain reference.

    python3 -m pytest benchmarks/chip/tests
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import reference  # noqa: E402
from deploy import Answer, uniform_pdn  # noqa: E402

telemetry = bench.load_module("generators", "telemetry")

# the tests compile for the CPU: keep those programs out of the cache
jax.config.update("jax_enable_compilation_cache", False)

# the configurations' shapes with fewer nodes per level
SMALL = {"hall12k": [2, 3, 4, 8]}
SEED = 2**31 + 17
SECONDS = 1.0


def cell(name: str, **override):
    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    cfg.update(fanout=SMALL[name], **override)
    mix = json.loads((HERE / "mixes" / "diurnal.json").read_text())
    return cfg, mix


@pytest.mark.parametrize("name", sorted(SMALL))
def test_program_is_correct(name):
    cfg, mix = cell(name)
    r = bench.run(cfg, mix, SEED, SECONDS)
    assert bench.is_correct(r, cfg["limits"]), r.compared
    assert len(r.satisfaction) > 0


@pytest.mark.parametrize("name", sorted(SMALL))
def test_float32_control_is_not_correct(name):
    cfg, mix = cell(name, precision="float32")
    r = bench.run(cfg, mix, SEED, SECONDS)
    assert not bench.is_correct(r, cfg["limits"]), r.compared


def stale(step):
    """The step hands back the previous interval's caps: its state never
    moves on."""
    last = []

    def broken(tele):
        ans = step(tele)
        last.append(ans)
        return last[-2] if len(last) > 1 else ans

    return broken


def half(step):
    """Half of the devices are left out of the solve (read as idle)."""

    def broken(tele):
        tele = tele.copy()
        tele[tele.size // 2 :] = 0.0
        return step(tele)

    return broken


def altered(step):
    """One cap changes by 1 W where the answer is produced."""

    def broken(tele):
        ans = step(tele)
        alloc = ans.allocation.copy()
        alloc[alloc.size // 3] -= 1.0
        return Answer(alloc, ans.phase1, ans.phase2, ans.pdhg_iters)

    return broken


@pytest.mark.parametrize("fault", [stale, half, altered], ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", sorted(SMALL))
def test_fault_is_not_correct(name, fault):
    cfg, mix = cell(name)
    r = bench.run(cfg, mix, SEED, SECONDS, wrap_step=fault)
    assert not bench.is_correct(r, cfg["limits"]), r.compared


@pytest.mark.parametrize("name", sorted(SMALL))
def test_full_size_tree_matches_reference(name):
    """The program's tree, built from the configuration, has the caps the
    reference derives on its own, at the cell's full size."""
    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    pdn = uniform_pdn(cfg)
    tree = reference.Tree(cfg["fanout"], cfg["oversub"], cfg["l"], cfg["u"])
    assert pdn.n == tree.n
    for d in range(len(tree.fanout)):
        caps = pdn.node_cap[pdn.node_depth == d]
        np.testing.assert_array_equal(caps, tree.node_caps(d))


def test_hall_is_the_papers_hall():
    from repro.pdn.tree import build_datacenter

    cfg = json.loads((HERE / "configs" / "hall12k.json").read_text())
    ours, theirs = uniform_pdn(cfg), build_datacenter()
    np.testing.assert_array_equal(ours.node_cap, theirs.node_cap)
    np.testing.assert_array_equal(ours.node_start, theirs.node_start)
    np.testing.assert_array_equal(ours.node_end, theirs.node_end)


def test_reference_matches_exact_waterfill():
    """Phases II/III as projections equal progressive filling, written out
    plainly here, on a small tree."""
    tree = reference.Tree([2, 3, 4], [0.9, 0.8, 1.0], 200.0, 700.0)
    rng = np.random.default_rng(3)
    base = rng.uniform(200, 450, tree.n)
    x = reference.project(tree, base + 1000.0, base, tree.u)
    y = base.copy()
    live = np.ones(tree.n, bool)
    while live.any():
        rates = [tree.u[live] - y[live]]
        for d, b in enumerate(tree.block):
            slack = tree.cap[d] - y.reshape(-1, b).sum(1)
            n = live.reshape(-1, b).sum(1)
            rates.append(np.where(n > 0, slack / np.maximum(n, 1), np.inf))
        t = min(r.min() for r in rates)
        y[live] += t
        done = tree.u - y <= 1e-9
        for d, b in enumerate(tree.block):
            tight = tree.cap[d] - y.reshape(-1, b).sum(1) <= 1e-9
            done |= np.repeat(tight, b)
        live &= ~done
    np.testing.assert_allclose(x, y, atol=1e-8)


def test_every_seed_offers_the_same_work():
    _, mix = cell("hall12k")
    n = 2 * 3 * 4 * 8 * 16
    a = telemetry.Telemetry(mix, n, 3, 5)
    b = telemetry.Telemetry(mix, n, 3, 2**31 + 5)
    again = telemetry.Telemetry(mix, n, 3, 5)
    np.testing.assert_array_equal(a.power(300), again.power(300))
    np.testing.assert_array_equal(a.job_of, b.job_of)  # same jobs, same devices
    demand = [np.clip(s.power(300), 200, 700).sum() for s in (a, b)]
    assert abs(demand[0] - demand[1]) < 0.01 * demand[0]


@pytest.mark.parametrize("hold", [1, 3])
def test_ring_is_one_segment_per_placement(hold):
    _, mix = cell("hall12k")
    mix = dict(mix, trace_seeds=[4, 9, 2], segment=6, hold=hold)
    n = 2 * 3 * 4 * 8
    ring = telemetry.replay(mix, n, 11)
    assert ring.shape == (18, n)
    for k, ts in enumerate(mix["trace_seeds"]):
        sim = telemetry.Telemetry(mix, n, ts, 11)
        t0 = mix["start"] + 6 * k
        for i in range(6):
            want = sim.power(t0 + (i // hold) * hold)
            np.testing.assert_array_equal(ring[6 * k + i], want)


def test_traced_positions_spread_over_the_ring():
    pos = sorted(bench.trace_positions(64))
    assert pos == [0, 9, 18, 27, 36, 45, 54, 63]
    assert sorted(bench.trace_positions(5)) == [0, 1, 2, 3, 4]


def test_traced_run_reads_every_traced_interval(tmp_path, monkeypatch):
    """The traced run's control flow on the CPU: one profiler session per
    traced ring position, each reduced (the CPU trace holds no TPU plane, so
    the device numbers stay empty)."""
    monkeypatch.setattr(bench, "RUNS", tmp_path)
    cfg, mix = cell("hall12k")
    r = bench.run(cfg, mix, SEED, 0.1, traced=True)
    assert set(r.traced) == bench.trace_positions(64)
    assert set(r.trace_parts) == set(r.traced)
    assert r.trace is None
    assert bench.is_correct(r, cfg["limits"]), r.compared
