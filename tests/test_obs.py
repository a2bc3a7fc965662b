"""Control-plane flight recorder (PR 8).

Covers the observability contract:

* ring-buffer wraparound keeps the most recent ``capacity`` rows in time
  order with counters spanning the whole run;
* log-bucket histogram edges (zero/denormal -> bucket 0, overflow clips);
* flushed flight rows agree with a host-side oracle on the engine path,
  under vmap (``step_batched`` lanes), and under shard_map (sharded fleet
  dispatch in a forced 8-device subprocess — conftest forbids XLA_FLAGS in
  this process);
* recording adds ZERO retraces once warm and bounded wall overhead (loose
  local bound; the 1.05x CI gate lives in ``benchmarks/obs_bench.py``);
* host spans nest, drain, and stay off by default;
* the report CLI renders a recorded run end to end.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as engine_mod
from repro.core.engine import AllocEngine
from repro.fleet import orchestrator as orch_mod
from repro.fleet.orchestrator import FleetOrchestrator
from repro.obs import export, recorder, report, spans
from repro.obs.stats import StepStats
from repro.pdn.hierarchy_gen import homogeneous_fleet
from repro.pdn.tree import build_from_level_sizes


def _powers(n, steps, seed=0, lo=50.0, hi=800.0):
    rng = np.random.default_rng(seed)
    return [rng.uniform(lo, hi, n) for _ in range(steps)]


# -- ring buffer + histogram mechanics ------------------------------------


def test_ring_wraparound_keeps_latest_in_time_order(small_pdn):
    """7 steps into a capacity-4 ring: rows 3..6 survive, oldest first;
    counters span all 7 steps."""
    cfg = recorder.RecorderConfig(capacity=4)
    eng = AllocEngine(small_pdn, recorder=cfg)
    for p in _powers(small_pdn.n, 7):
        eng.step(p)
    flight = eng.flush_recorder()["step"]
    assert flight["counters"]["n_steps"] == 7
    steps = flight["rows"][:, recorder.FIELDS.index("step")].astype(int)
    assert steps.tolist() == [3, 4, 5, 6]


def test_flush_before_wraparound_returns_partial_ring(small_pdn):
    cfg = recorder.RecorderConfig(capacity=8)
    eng = AllocEngine(small_pdn, recorder=cfg)
    for p in _powers(small_pdn.n, 3):
        eng.step(p)
    flight = eng.flush_recorder()["step"]
    assert flight["rows"].shape[0] == 3
    steps = flight["rows"][:, recorder.FIELDS.index("step")].astype(int)
    assert steps.tolist() == [0, 1, 2]


def test_flush_idempotent_and_reset_clears(small_pdn):
    eng = AllocEngine(small_pdn, recorder=True)
    for p in _powers(small_pdn.n, 2):
        eng.step(p)
    a = eng.flush_recorder()["step"]
    b = eng.flush_recorder()["step"]
    np.testing.assert_array_equal(a["rows"], b["rows"])
    eng.flush_recorder(reset=True)
    assert eng.flush_recorder() == {}
    eng.step(_powers(small_pdn.n, 1)[0])  # lazily re-inits
    assert eng.flush_recorder()["step"]["counters"]["n_steps"] == 1


def test_log_bucket_edges():
    """Bucket b holds [10**(lo+b), 10**(lo+b+1)); zero floors, huge clips."""
    cfg = recorder.RecorderConfig()  # lo_exp=-12, 16 buckets

    def bucket(v):
        return int(recorder.log_bucket(jnp.asarray(v, jnp.float32), cfg))

    assert bucket(0.0) == 0
    assert bucket(1e-12) == 0
    assert bucket(9.99e-12) == 0
    assert bucket(1e-11) == 1
    assert bucket(1.0) == 12
    assert bucket(1e30) == cfg.buckets - 1


# -- flush parity vs host oracle ------------------------------------------


def test_engine_flight_matches_host_oracle(small_pdn):
    """Per-row gauges agree with quantities recomputed on the host from the
    step results the engine returned."""
    eng = AllocEngine(small_pdn, recorder=True)
    allocs, stats = [], []
    for p in _powers(small_pdn.n, 5):
        res = eng.step(p)
        allocs.append(res.allocation)
        stats.append(res.stats)
    rows = recorder.rows_as_dicts(eng.flush_recorder()["step"])
    assert len(rows) == 5
    for t, row in enumerate(rows):
        assert row["step"] == t
        assert row["iterations"] == stats[t]["total_iterations"]
        assert row["skipped"] == int(stats[t]["skipped"])
        assert row["converged"] == int(stats[t]["converged"])
        assert row["alloc_W"] == pytest.approx(float(allocs[t].sum()), rel=1e-9)
        move = 0.0 if t == 0 else float(np.abs(allocs[t] - allocs[t - 1]).max())
        assert row["grant_move"] == pytest.approx(move, rel=1e-9, abs=1e-12)
        assert 0.0 < row["satisfaction"] <= 1.0
        assert row["tier"] in (0, 1, 2)


def test_batched_lanes_match_single_engine(small_pdn):
    """vmap path: each [K] recorder lane reproduces the single-lane flight
    of an engine fed that lane's telemetry."""
    K, steps = 3, 4
    tele = [
        np.stack([p * (1.0 + 0.1 * k) for k in range(K)])
        for p in _powers(small_pdn.n, steps, seed=3)
    ]
    batched = AllocEngine(small_pdn, recorder=True)
    for tb in tele:
        batched.step_batched(tb)
    lanes = batched.flush_recorder()["batched"][K]
    assert len(lanes) == K
    i_alloc = recorder.FIELDS.index("alloc_W")
    i_iters = recorder.FIELDS.index("iterations")
    for k in range(K):
        solo = AllocEngine(small_pdn, recorder=True)
        for tb in tele:
            solo.step_batched(tb[k : k + 1])
        ref = solo.flush_recorder()["batched"][1][0]
        assert lanes[k]["counters"]["n_steps"] == steps
        np.testing.assert_allclose(
            lanes[k]["rows"][:, i_alloc], ref["rows"][:, i_alloc], rtol=1e-9
        )
        np.testing.assert_array_equal(
            lanes[k]["rows"][:, i_iters], ref["rows"][:, i_iters]
        )


def test_fleet_stacked_flight_and_flush(small_pdn):
    orch = FleetOrchestrator(small_pdn, level=1, mode="stacked", recorder=True)
    steps = 3
    for p in _powers(small_pdn.n, steps, seed=5):
        orch.step(p)
    flight = orch.flush_recorder()
    assert flight["mode"] == "stacked"
    assert len(flight["lanes"]) == orch.k
    for lane in flight["lanes"]:
        assert lane["counters"]["n_steps"] == steps
        assert lane["rows"].shape[0] == steps


_SHARDED_PARITY_SCRIPT = r"""
import json, sys
import numpy as np
from repro.fleet.orchestrator import FleetOrchestrator
from repro.pdn.hierarchy_gen import homogeneous_fleet

pdn = homogeneous_fleet(4)
rng = np.random.default_rng(11)
tele = [rng.uniform(100.0, 700.0, pdn.n) for _ in range(3)]
out = {}
for mode in ("stacked", "sharded"):
    orch = FleetOrchestrator(pdn, level=1, mode=mode, recorder=True)
    for p in tele:
        orch.step(p)
    flight = orch.flush_recorder()
    out[mode] = [lane["rows"].tolist() for lane in flight["lanes"]]
print(json.dumps(out))
"""


def test_sharded_flight_matches_stacked_subprocess():
    """shard_map path on a forced 8-device CPU mesh: per-lane flight rows
    match stacked dispatch (the recorder shards with its domains and only
    gathers at flush)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_PARITY_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    stacked = [np.asarray(lane) for lane in out["stacked"]]
    sharded = [np.asarray(lane) for lane in out["sharded"]]
    assert len(stacked) == len(sharded) > 0
    i_alloc = recorder.FIELDS.index("alloc_W")
    i_tier = recorder.FIELDS.index("tier")
    for ls, lh in zip(stacked, sharded):
        assert ls.shape == lh.shape
        np.testing.assert_allclose(ls[:, i_alloc], lh[:, i_alloc], rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(ls[:, i_tier], lh[:, i_tier])


# -- zero retraces + bounded overhead -------------------------------------


def test_engine_recording_zero_retraces(small_pdn):
    eng = AllocEngine(small_pdn, recorder=True)
    powers = _powers(small_pdn.n, 7, seed=7)
    eng.step(powers[0])
    eng.step(powers[1])
    before = engine_mod.trace_count()
    for p in powers[2:]:
        eng.step(p)
    assert engine_mod.trace_count() == before


def test_fleet_stacked_recording_zero_retraces(small_pdn):
    orch = FleetOrchestrator(small_pdn, level=1, mode="stacked", recorder=True)
    powers = _powers(small_pdn.n, 6, seed=9)
    orch.step(powers[0])
    orch.step(powers[1])
    before = orch_mod.trace_count()
    for p in powers[2:]:
        orch.step(p)
    assert orch_mod.trace_count() == before


def test_recording_overhead_loosely_bounded(small_pdn):
    """Warm recorded steps stay within 2x of unrecorded ones even on this
    toy fleet, where the recorder's small constant cost is at its relative
    worst.  The real 1.05x gate runs on the representative CI geometry in
    benchmarks/obs_bench.py."""
    base = AllocEngine(small_pdn)
    rec = AllocEngine(small_pdn, recorder=True)
    powers = _powers(small_pdn.n, 5, seed=13)
    for eng in (base, rec):
        eng.step(powers[0])
        eng.step(powers[1])
    best = {id(base): np.inf, id(rec): np.inf}
    for rep in range(6):
        for eng in (base, rec) if rep % 2 == 0 else (rec, base):
            t0 = time.perf_counter()
            for p in powers:
                eng.step(p)
            best[id(eng)] = min(best[id(eng)], time.perf_counter() - t0)
    assert best[id(rec)] <= 2.0 * best[id(base)]


# -- host spans ------------------------------------------------------------


def test_spans_disabled_by_default_and_nest_when_enabled():
    spans.reset()
    with spans.span("never"):
        pass
    assert spans.drain() == []
    spans.enable()
    try:
        with spans.span("outer"):
            with spans.span("inner"):
                pass
        recs = spans.drain()
    finally:
        spans.disable()
    paths = [r["span"] for r in recs]
    assert "outer" in paths
    assert "outer/inner" in paths
    summ = spans.summary(recs)
    assert summ["outer"]["count"] == 1
    assert summ["outer/inner"]["p95_ms"] >= 0.0


def test_orchestrator_emits_stage_spans(small_pdn):
    spans.reset()
    spans.enable()
    try:
        orch = FleetOrchestrator(small_pdn, level=1, mode="stacked")
        orch.step(_powers(small_pdn.n, 1, seed=17)[0])
        paths = {r["span"] for r in spans.drain()}
    finally:
        spans.disable()
    assert "fleet.plan" in paths
    assert "fleet.dispatch" in paths
    assert any(p.startswith("fleet.plan/coordinator.") for p in paths)


ENGINE_SPANS = [
    "engine.step/engine.prepare",
    "engine.step/engine.upload",
    "engine.step/engine.dispatch",
    "engine.step/engine.wait",
    "engine.step/engine.fetch",
    "engine.step/engine.stats",
]


def test_engine_step_spans_nest_in_order_and_cover_the_wall(small_pdn):
    eng = AllocEngine(small_pdn)
    p = _powers(small_pdn.n, 1, seed=29)[0]
    eng.step(p)  # compile the cold and the warm-carry programs
    eng.step(p)
    spans.reset()
    spans.enable()
    try:
        res = eng.step(p)
        recs = spans.drain()
    finally:
        spans.disable()
    # a span is recorded when it closes: children in order, then the step
    assert [r["span"] for r in recs] == ENGINE_SPANS + ["engine.step"]
    outer = recs[-1]
    t_end = outer["t0"] + outer["ms"] / 1e3
    for r in recs[:-1]:
        assert outer["t0"] <= r["t0"] <= r["t0"] + r["ms"] / 1e3 <= t_end
    for a, b in zip(recs[:-2], recs[1:-1]):
        assert a["t0"] + a["ms"] / 1e3 <= b["t0"]
    # wall_time_s covers the whole step, from engine.prepare into engine.stats
    assert recs[-2]["t0"] - recs[0]["t0"] <= res.wall_time_s <= outer["ms"] / 1e3
    assert eng.history[-1]["wall_s"] == res.wall_time_s


def test_disabled_spans_record_nothing_and_open_no_annotation(small_pdn, monkeypatch):
    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(spans.jax.profiler, "TraceAnnotation", Annotation)
    eng = AllocEngine(small_pdn)
    p = _powers(small_pdn.n, 1, seed=31)[0]
    spans.reset()
    eng.step(p)
    assert spans.drain() == [] and opened == []
    spans.enable()
    try:
        eng.step(p)
    finally:
        spans.disable()
    assert opened == ["engine.step"] + ENGINE_SPANS
    assert len(spans.drain()) == len(opened)


def test_engine_spans_share_the_profiler_host_line(small_pdn, tmp_path):
    """Inside a profiler session the enabled spans land as annotations on
    the host thread that opened the caller's own span around the step."""
    import jax
    from jax.profiler import ProfileData

    eng = AllocEngine(small_pdn)
    p = _powers(small_pdn.n, 1, seed=37)[0]
    eng.step(p)
    spans.enable()
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("interval"):
                eng.step(p)
        finally:
            jax.profiler.stop_trace()
    finally:
        spans.disable()
        spans.reset()
    (path,) = tmp_path.rglob("*.xplane.pb")
    lines = [
        {e.name: (e.start_ns, e.start_ns + e.duration_ns) for e in ln.events}
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name == "/host:CPU"
        for ln in plane.lines
    ]
    (line,) = [ev for ev in lines if "interval" in ev]
    a, b = line["interval"]
    for name in ["engine.step"] + ENGINE_SPANS:
        s, e = line[name]
        assert a <= s <= e <= b


FLEET_SPANS = [
    "fleet.dispatch/fleet.scatter",
    "fleet.dispatch/fleet.plan",
    "fleet.dispatch/fleet.upload",
    "fleet.dispatch/fleet.wait",
    "fleet.dispatch/fleet.fetch",
    "fleet.dispatch/fleet.stats",
]


def test_sharded_fleet_spans_nest_in_order(small_pdn):
    orch = FleetOrchestrator(small_pdn, level=1, mode="sharded")
    p = _powers(small_pdn.n, 1, seed=41)[0]
    orch.step(p)  # compile the cold and the warm-carry programs
    orch.step(p)
    spans.reset()
    spans.enable()
    try:
        res = orch.step(p)
        recs = spans.drain()
    finally:
        spans.disable()
    assert [r["span"] for r in recs] == FLEET_SPANS + ["fleet.dispatch"]
    outer = recs[-1]
    t_end = outer["t0"] + outer["ms"] / 1e3
    for a, b in zip(recs[:-2], recs[1:-1]):
        assert outer["t0"] <= a["t0"] <= a["t0"] + a["ms"] / 1e3 <= b["t0"] <= t_end
    # wall_time_s covers the dispatch, from fleet.scatter into fleet.stats
    assert recs[-2]["t0"] - recs[0]["t0"] <= res.wall_time_s


def test_disabled_fleet_spans_open_no_annotation(small_pdn, monkeypatch):
    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(spans.jax.profiler, "TraceAnnotation", Annotation)
    orch = FleetOrchestrator(small_pdn, level=1, mode="sharded")
    p = _powers(small_pdn.n, 1, seed=43)[0]
    spans.reset()
    orch.step(p)
    assert spans.drain() == [] and opened == []
    spans.enable()
    try:
        orch.step(p)
    finally:
        spans.disable()
    assert opened == ["fleet.dispatch"] + FLEET_SPANS
    assert len(spans.drain()) == len(opened)


def test_sharded_fleet_answers_do_not_depend_on_spans(small_pdn):
    on, off = (FleetOrchestrator(small_pdn, level=1, mode="sharded") for _ in "ab")
    for p in _powers(small_pdn.n, 2, seed=47):
        spans.enable()
        try:
            a = on.step(p)
        finally:
            spans.disable()
            spans.reset()
        b = off.step(p)
        for name in ("allocation", "phase1", "phase2", "grants", "demand"):
            np.testing.assert_array_equal(getattr(a, name), getattr(b, name))
        assert a.stats["coordinator_rounds"] == b.stats["coordinator_rounds"]


def test_coordinator_rounds_count_both_grant_passes():
    """``coordinator_rounds`` is the rounds of the plan's demand pass plus
    its headroom pass, as direct ``waterfill_jax`` calls on the step's own
    demand count them; the history carries it with the per-domain
    iteration extremes."""
    import jax

    from repro.core.treeops import TreeTopo
    from repro.core.waterfill import waterfill_jax

    pdn = homogeneous_fleet(4, root_oversub=0.8)  # a feed the grants share
    orch = FleetOrchestrator(pdn, level=1, mode="sharded")
    res = orch.step(_powers(pdn.n, 1, seed=53, lo=300.0, hi=700.0)[0])
    dcap, ccap, dmin = orch._effective_domain_caps()
    with jax.enable_x64(True):
        ctree = TreeTopo(
            start=jnp.asarray(orch.coordinator.start),
            end=jnp.asarray(orch.coordinator.end),
            cap=jnp.asarray(ccap),
            depth=jnp.zeros(ccap.shape[0], jnp.int32),
        )
        mask = jnp.ones(orch.k, bool)
        want = jnp.clip(jnp.asarray(res.demand), dmin, dcap)
        grants, demand_rounds = waterfill_jax(jnp.asarray(dmin), mask, ctree, want)
        grants, headroom_rounds = waterfill_jax(grants, mask, ctree, jnp.asarray(dcap))
    rounds = int(demand_rounds) + int(headroom_rounds)
    assert int(demand_rounds) > 0 and int(headroom_rounds) > 0
    assert res.stats["coordinator_rounds"] == rounds
    np.testing.assert_allclose(res.grants, np.asarray(grants), rtol=0, atol=1e-9)
    row = orch.history[-1]
    iters = np.asarray(res.stats["iterations"])
    assert row["coordinator_rounds"] == rounds
    assert (row["iterations_max"], row["iterations_min"]) == (iters.max(), iters.min())


# -- StepStats consolidation ----------------------------------------------


def test_stepstats_aliases_and_attr_access(small_pdn):
    eng = AllocEngine(small_pdn)
    res = eng.step(_powers(small_pdn.n, 1, seed=19)[0])
    st = res.stats
    assert isinstance(st, StepStats)
    assert st["total_iterations"] == st["iterations"] == st.iterations
    assert st["total_solves"] == st["solves"]
    assert list(st["phase_iterations"]) == list(st["iterations_per_phase"])
    # plain-dict consumers keep working
    assert json.dumps({k: 0 for k in st}) is not None


# -- exporters + report CLI ------------------------------------------------


def test_jsonl_roundtrip_and_report_cli(tmp_path, small_pdn, capsys):
    eng = AllocEngine(small_pdn, recorder=True)
    walls = []
    for p in _powers(small_pdn.n, 4, seed=23):
        walls.append(1000.0 * eng.step(p).wall_time_s)
    rows = export.flight_rows(eng.flush_recorder()["step"], walls_ms=walls)
    path = tmp_path / "flight.jsonl"
    export.write_jsonl(path, rows)
    back = export.read_jsonl(path)
    assert back == rows
    assert all("wall_ms" in r for r in back)

    summary = report.summarize(back)
    assert summary["steps"] == 4
    assert 0.0 <= summary["certified_fraction"] <= 1.0
    assert "p99" in summary["wall_ms"]
    text = report.render(summary)
    assert "certify tiers" in text
    assert "interval wall" in text

    prom = tmp_path / "metrics.prom"
    assert report.main([str(path), "--prom", str(prom)]) == 0
    out = capsys.readouterr().out
    assert "flight record: 4 steps" in out
    assert "repro_steps_total 4" in prom.read_text()


def test_prometheus_text_from_live_state(small_pdn):
    eng = AllocEngine(small_pdn, recorder=True)
    for p in _powers(small_pdn.n, 3, seed=29):
        eng.step(p)
    text = export.prometheus_text(eng.flush_recorder()["step"])
    assert "repro_steps_total 3" in text
    assert "# TYPE" in text
