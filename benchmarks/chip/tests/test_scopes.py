"""Scope and span readers against numbers worked out by hand, and the
engine's spans in a real CPU profiler session.

    python3 -m pytest benchmarks/chip/tests
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import devtrace  # noqa: E402
import scopes  # noqa: E402
import step_profile  # noqa: E402
from test_devtrace import as_trace  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

HLO = """\
%fused_computation (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0.1, %param_0.1), metadata={op_name="jit(f)/phase2/waterfill/while/body/add"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %while.3 = (s32[], f32[8]{0}) while(%tuple.2), condition=%cond, body=%body, metadata={op_name="jit(f)/phase2/waterfill/while"}
  %fusion.7 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/phase2/waterfill/while/body/add"}
  %fusion.8 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/phase1/while/body/jit(solve)/pdhg/mul" stack_frame_id=4}
  ROOT %copy.9 = f32[8]{0} copy(%fusion.8), metadata={op_name="jit(f)/phase1/repair/mul"}
}
"""


def test_op_names_reads_each_instruction_s_metadata():
    names = scopes.op_names(HLO)
    assert names["fusion.7"] == "jit(f)/phase2/waterfill/while/body/add"
    assert names["fusion.8"] == "jit(f)/phase1/while/body/jit(solve)/pdhg/mul"
    assert names["copy.9"] == "jit(f)/phase1/repair/mul"
    assert "x" not in names  # no metadata
    assert scopes.in_scope(names["fusion.8"], "pdhg")
    assert not scopes.in_scope("jit(f)/phase1/pdhg_x/mul", "pdhg")


def test_scope_ms_hand_worked_trace():
    # intervals [0, 10] and [20, 30]; device 0 runs the waterfill loop
    # [1, 7] holding two fusions [1, 3] and [4, 7], the PDHG fusion [8, 9]
    # and [21, 26], a repair copy [26, 27] and a fusion between intervals;
    # device 1 runs the PDHG fusion [2, 6]
    host = {"names": ["interval"], "id": [0, 0], "start": [0.0, 20.0],
            "end": [10.0, 30.0]}
    dev0 = {
        "names": ["%while.3", "%fusion.7", "%fusion.8", "%copy.9"],
        "id": [0, 1, 1, 2, 2, 3, 2],
        "start": [1.0, 1.0, 4.0, 8.0, 21.0, 26.0, 12.0],
        "end": [7.0, 3.0, 7.0, 9.0, 26.0, 27.0, 14.0],
    }
    dev1 = {"names": ["%fusion.8"], "id": [0], "start": [2.0], "end": [6.0]}
    tr = as_trace({"devices": [dev0, dev1], "host": host})
    names = scopes.op_names(HLO)
    # waterfill: device 0 (5 + 0) / 2, device 1 0: 1.25 s per interval
    assert scopes.scope_ms(tr, names, "waterfill") == pytest.approx(1.25e3)
    # pdhg: device 0 (1 + 5) / 2 = 3, device 1 (4 + 0) / 2 = 2
    assert scopes.scope_ms(tr, names, "pdhg") == pytest.approx(2.5e3)
    assert scopes.scope_ms(tr, names, "repair") == pytest.approx(0.25e3)
    # disjoint scopes add up to at most the device time
    red = devtrace.reduce(tr)
    parts = sum(scopes.scope_ms(tr, names, s) for s in ("waterfill", "pdhg", "repair"))
    assert parts <= red["device_ms"] + 1e-9
    assert scopes.matched_share(tr, names) == pytest.approx(1.0)
    assert scopes.matched_share(tr, {"fusion.8": "x"}) == pytest.approx(12.0 / 18.0)
    assert scopes.scope_ms(as_trace({"devices": [], "host": host}), names, "pdhg") is None


def test_host_ms_is_each_step_less_its_wait():
    recs = [
        {"span": "engine.step/engine.prepare", "ms": 1.0},
        {"span": "engine.step/engine.wait", "ms": 30.0},
        {"span": "engine.step", "ms": 40.0},
        {"span": "engine.step/engine.wait", "ms": 10.0},
        {"span": "engine.step/engine.fetch", "ms": 2.0},
        {"span": "engine.step", "ms": 15.0},
    ]
    assert scopes.host_ms(recs) == [10.0, 5.0]


def test_engine_spans_name_the_host_line_in_a_profiler_session(tmp_path):
    """The engine's spans sit on the host line of the benchmark's interval
    span; with the host events cut to them, ``devtrace._label`` names a
    moment of the step by the innermost span, where the full line names
    the Python frame the profiler's tracer recorded there."""
    from repro.core.engine import AllocEngine
    from repro.obs import spans
    from repro.pdn.tree import build_from_level_sizes

    pdn = build_from_level_sizes([2, 3, 2], gpus_per_server=4)
    eng = AllocEngine(pdn)
    tele = np.random.default_rng(3).uniform(50.0, 800.0, pdn.n)
    eng.step(tele)
    eng.step(tele)
    spans.reset()
    spans.enable()
    try:
        jax.profiler.start_trace(str(tmp_path))
        with jax.profiler.TraceAnnotation(devtrace.SPAN):
            eng.step(tele)
        jax.profiler.stop_trace()
        recs = {r["span"]: r for r in spans.drain()}
    finally:
        spans.disable()
    tr = devtrace.load(tmp_path)
    host, cut = tr["host"], scopes.program_spans(tr)["host"]
    assert devtrace.SPAN in host["names"]
    frames = 0
    for s in step_profile.SPANS:
        path = f"engine.step/engine.{s}"
        (i,) = np.flatnonzero(np.asarray(host["names"])[host["id"]] == path)
        mid = (host["start"][i] + host["end"][i]) / 2
        assert devtrace._label(cut, mid) == path
        frames += not devtrace._label(host, mid).startswith("engine.")
    assert frames > 0
    assert set(recs) == {"engine.step"} | {
        f"engine.step/engine.{s}" for s in step_profile.SPANS
    }


def test_op_names_match_the_operations_the_program_runs(tmp_path):
    """The compiled text's instruction names are those the profiler gives
    the program's operations (on the CPU, the ``hlo_op`` of its events)."""
    from jax.profiler import ProfileData

    @jax.jit
    def f(x):
        with jax.named_scope("waterfill"):
            y = jax.lax.fori_loop(0, 3, lambda i, a: a * 1.5 + jnp.sin(a), x)
        with jax.named_scope("pdhg"):
            return jnp.cos(y).sum()

    x = jnp.ones(64)
    names = scopes.op_names(f.lower(x).compile().as_text())
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    ran = {
        dict(e.stats)["hlo_op"]
        for plane in ProfileData.from_file(str(path)).planes
        for line in plane.lines
        for e in line.events
        if dict(e.stats).get("hlo_module") == "jit_f"
    }
    scoped = {n for n in ran if n in names}
    assert any(scopes.in_scope(names[n], "waterfill") for n in scoped)
    assert any(scopes.in_scope(names[n], "pdhg") for n in scoped)


def test_step_profile_runs_a_small_hall():
    cfg = json.loads((HERE.parent / "configs" / "hall12k.json").read_text())
    cfg["fanout"] = [2, 3, 4, 8]
    mix = json.loads((HERE.parent / "mixes" / "diurnal.json").read_text())
    out = step_profile.run(cfg, mix, 2**31 + 17, 0.5, 1)
    off, on = out["windows"]
    assert not off["spans"] and on["spans"]
    assert on["host_ms"] > 0 and on["waterfill_rounds"] > 0
    traced = out["traced"]["positions"]
    assert sorted(traced) == sorted(step_profile.bench.trace_positions(64))
    assert out["traced"]["waterfill_rounds"] > 0
    for row in traced.values():
        inner = sum(row[s] for s in step_profile.SPANS)
        assert inner <= row["step_ms"] <= row["wall_ms"]
        assert len(row["waterfill_rounds"]) == 2
