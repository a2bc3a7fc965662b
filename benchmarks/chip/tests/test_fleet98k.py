"""The campus cell's comparison, on the CPU at a size a test run holds.

``fleet98k`` through ``bench.run`` with fewer nodes per level: the sharded
``FleetOrchestrator`` on whatever devices JAX has here (one CPU device: a
one-shard mesh), the mix's telemetry, warm-up, the timed loop and the
comparison with the plain reference of the whole campus tree.

    python3 -m pytest benchmarks/chip/tests/test_fleet98k.py
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import numpy as np

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import reference  # noqa: E402
from deploy import uniform_pdn  # noqa: E402

# the tests compile for the CPU: keep those programs out of the cache
jax.config.update("jax_enable_compilation_cache", False)

SMALL = [4, 2, 3, 4, 8]  # the campus's five levels, fewer nodes per level
SEED = 2**31 + 29
SECONDS = 1.0


def cell(**override):
    cfg = json.loads((HERE / "configs" / "fleet98k.json").read_text())
    cfg.update(fanout=SMALL, **override)
    mix = json.loads((HERE / "mixes" / "diurnal.json").read_text())
    return cfg, mix


def test_campus_is_correct():
    cfg, mix = cell()
    r = bench.run(cfg, mix, SEED, SECONDS)
    assert bench.is_correct(r, cfg["limits"]), r.compared
    assert len(r.satisfaction) > 0
    assert min(r.pdhg_iters) > 0


def test_campus_float32_control_is_not_correct():
    cfg, mix = cell(precision="float32")
    r = bench.run(cfg, mix, SEED, SECONDS)
    assert not bench.is_correct(r, cfg["limits"]), r.compared


def shifted_phase2(step):
    """One domain's Phase II caps are 1 W higher than the program made them."""

    def broken(tele):
        ans = step(tele)
        phase2 = ans.phase2.copy()
        phase2[: phase2.size // SMALL[0]] += 1.0
        return ans._replace(phase2=phase2)

    return broken


def test_campus_fault_is_not_correct():
    cfg, mix = cell()
    r = bench.run(cfg, mix, SEED, SECONDS, wrap_step=shifted_phase2)
    assert not bench.is_correct(r, cfg["limits"]), r.compared


def test_full_size_campus_is_eight_halls():
    """At its full size the campus tree has the caps the reference derives,
    and every hall under it is the hall cell's tree."""
    cfg = json.loads((HERE / "configs" / "fleet98k.json").read_text())
    hall = json.loads((HERE / "configs" / "hall12k.json").read_text())
    pdn = uniform_pdn(cfg)
    tree = reference.Tree(cfg["fanout"], cfg["oversub"], cfg["l"], cfg["u"])
    assert (pdn.n, pdn.m) == (98_304, 13_097)
    for d in range(len(tree.fanout)):
        caps = pdn.node_cap[pdn.node_depth == d]
        np.testing.assert_array_equal(caps, tree.node_caps(d))
    assert cfg["fanout"][cfg["level"] :] == hall["fanout"]
    one = uniform_pdn(hall)
    np.testing.assert_array_equal(pdn.node_cap[pdn.node_depth == 1], one.node_cap[0])
    # the campus feed carries the halls' feeds: it never binds
    assert tree.cap[0] == cfg["fanout"][0] * one.node_cap[0]


def test_collective_ms_reads_nothing_without_a_trace():
    read = bench.load_module("metrics", "collective_ms").read
    r = bench.Run()
    assert read(r) is None
    r.trace = {"collective_ms": 0.25}
    assert read(r) == 0.25
