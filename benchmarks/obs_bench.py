"""Flight-recorder overhead gate (PR 8): the in-jit step telemetry must be
effectively free — same compiled program shape, zero extra retraces, and
<= 5% warm-step wall overhead on the engine smoke loop.

Two identical engines serve the same telemetry stream, one recording and
one not; warm per-step wall is measured min-of-repeats (robust to CI runner
noise) and the ratio is gated.  Retraces are counted with
``repro.core.engine.trace_count`` across the recorded stepping.

Emits ``BENCH_obs.json`` for CI's bench-smoke job (schema + acceptance
flags + the ``obs.overhead_headroom`` floor via ``check_bench.py``):

    PYTHONPATH=src python benchmarks/obs_bench.py [--out artifacts/bench]
"""

from __future__ import annotations

import time

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import engine as engine_mod
from repro.core.engine import AllocEngine
from repro.obs import report as obs_report
from repro.obs.export import flight_rows
from repro.pdn.telemetry import TelemetrySim, TraceConfig
from repro.pdn.tree import build_datacenter

OVERHEAD_BAR = 1.05


def _time_pair(base, rec, powers, reps: int) -> tuple[float, float]:
    """Per-step-minimum walls (s) for both engines, interleaved.

    Both variants serve the identical telemetry sequence; the estimator is
    the per-telemetry-step minimum across repeats, summed over the block —
    the least-noise wall estimate on a shared CI runner (block totals are
    dominated by scheduler jitter).  Interleaving the variants inside every
    repeat decorrelates slow machine-load drift from the variant."""
    n = len(powers)
    best = {id(base): np.full(n, np.inf), id(rec): np.full(n, np.inf)}
    for rep in range(reps):
        order = (base, rec) if rep % 2 == 0 else (rec, base)
        for eng in order:
            for i, p in enumerate(powers):
                t0 = time.perf_counter()
                eng.step(p)
                dt = time.perf_counter() - t0
                best[id(eng)][i] = min(best[id(eng)][i], dt)
    return float(best[id(base)].sum()), float(best[id(rec)].sum())


def run(steps: int = 8, reps: int = 6, seed: int = 0) -> dict:
    # same CI-smoke geometry as satisfaction_trace --smoke (n=512): the
    # recorder's per-step cost is a small constant (one ring write + scalar
    # gauges), so the gate measures it against a representative solve, not
    # a toy fleet whose whole step is sub-millisecond
    pdn = build_datacenter(n_halls=1, racks_per_hall=8, servers_per_rack=8)
    sim = TelemetrySim(TraceConfig(n_devices=pdn.n, seed=seed))
    powers = [sim.power(t) for t in range(steps)]

    base = AllocEngine(pdn)
    rec = AllocEngine(pdn, recorder=True)
    # cold-start both variants (compile + calibration) outside the clock
    for eng in (base, rec):
        eng.step(powers[0])
        eng.step(powers[1])

    traces_before = engine_mod.trace_count()
    base_s, rec_s = _time_pair(base, rec, powers, reps)
    retraces = engine_mod.trace_count() - traces_before

    overhead = rec_s / base_s
    flight = rec.flush_recorder()
    rows = flight_rows(flight["step"])
    summary = obs_report.summarize(rows)
    return {
        "n_devices": pdn.n,
        "steps": steps,
        "reps": reps,
        "base_ms_per_step": 1e3 * base_s / steps,
        "recorded_ms_per_step": 1e3 * rec_s / steps,
        "overhead_ratio": overhead,
        "overhead_bar": OVERHEAD_BAR,
        "retraces_while_recording": retraces,
        "flight_steps": len(rows),
        "certified_fraction": summary["certified_fraction"],
        "skip_rate": summary["skip_rate"],
        "satisfaction_p50": summary["satisfaction"]["p50"],
        "meets_overhead_le_1_05": bool(overhead <= OVERHEAD_BAR),
        "meets_zero_retraces": bool(retraces == 0),
        "meets_flight_complete": bool(
            len(rows) == int(flight["step"]["counters"]["n_steps"]) > 0
        ),
    }


def main() -> None:
    use_compile_cache()
    import argparse
    import json
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="artifacts/bench")
    args = ap.parse_args()

    res = run()
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, "BENCH_obs.json")
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    print(
        f"n={res['n_devices']}: base {res['base_ms_per_step']:.2f}ms vs "
        f"recorded {res['recorded_ms_per_step']:.2f}ms per step "
        f"(x{res['overhead_ratio']:.3f}, bar {OVERHEAD_BAR}); "
        f"retraces {res['retraces_while_recording']}; "
        f"{res['flight_steps']} flight rows; wrote {path}"
    )


if __name__ == "__main__":
    main()
