"""Paper Figure 2 / section 5.5: satisfaction ratio + relative utilization
improvement over the trace, nvPAX vs Static vs Greedy, plus runtime —
driven through the persistent :class:`repro.core.engine.AllocEngine`
control loop (construct once, step per interval; the rebuild-per-step host
path this bench used before PR 7 is exactly the pattern PR 2 deprecated).

Paper values on the proprietary trace: nvPAX mean S 98.92% (std 0.48, min
96.49, max 100), Static 81.30%, Greedy 98.92%; nvPAX >= Static on every
timestamp; mean wall 264.69 ms.

Emits the machine-readable ``BENCH_trace.json`` consumed by CI's
bench-smoke job (schema + acceptance flags + regression floors via
``check_bench.py``) plus ``FLIGHT_trace.jsonl``, the engine's in-jit
flight record (PR 8) — render it with ``python -m repro.obs.report``:

    PYTHONPATH=src python benchmarks/satisfaction_trace.py [--smoke|--full] \
        [--out artifacts/bench]
"""

from __future__ import annotations

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.engine import AllocEngine
from repro.core.greedy import greedy_allocate, static_allocate
from repro.core.metrics import relative_improvement, satisfaction_ratio
from repro.obs import export
from repro.pdn.telemetry import TelemetrySim, TraceConfig
from repro.pdn.tree import build_datacenter

PAPER = {
    "S_nvpax_mean": 98.92,
    "S_static_mean": 81.30,
    "S_greedy_mean": 98.92,
    "wall_ms_mean": 264.69,
}


def run(
    steps: int = 60,
    stride: int = 48,
    seed: int = 0,
    *,
    smoke: bool = False,
    flight_out: str | None = None,
) -> dict:
    """``steps`` control steps sampled every ``stride`` from the 3-day
    trace (stride 48 = 24 min -> covers diurnal structure in few steps).
    ``smoke`` shrinks the paper geometry to a CI-sized fleet.
    ``flight_out`` writes the engine's flight record (one JSONL row per
    control step, host walls merged in) for ``python -m repro.obs.report``."""
    pdn = (
        build_datacenter(n_halls=1, racks_per_hall=8, servers_per_rack=8)
        if smoke
        else build_datacenter()
    )
    sim = TelemetrySim(TraceConfig(n_devices=pdn.n, seed=seed))
    eng = AllocEngine(pdn, recorder=True)
    s_nv, s_st, s_gr, du_st, du_gr, wall = [], [], [], [], [], []
    for i in range(steps):
        power = sim.power(i * stride)
        res = eng.step(power)
        # the same request shaping the engine applies (paper section 5.2)
        act = power >= eng.idle_threshold
        r = np.where(act, np.clip(power, pdn.dev_l, pdn.dev_u), pdn.dev_l)
        a_st = static_allocate(pdn)
        a_gr = greedy_allocate(pdn, power)
        s_nv.append(satisfaction_ratio(r, res.allocation))
        s_st.append(satisfaction_ratio(r, a_st))
        s_gr.append(satisfaction_ratio(r, a_gr))
        du_st.append(relative_improvement(r, res.allocation, a_st))
        du_gr.append(relative_improvement(r, res.allocation, a_gr))
        wall.append(res.wall_time_s * 1000)
    s_nv, s_st, s_gr = map(np.asarray, (s_nv, s_st, s_gr))
    wall_warm = wall[1:]  # drop the compile step
    flight = eng.flush_recorder()
    rows = export.flight_rows(flight["step"], walls_ms=wall)
    if flight_out is not None:
        export.write_jsonl(flight_out, rows)
    out = {
        "steps": steps,
        "stride": stride,
        "n_devices": pdn.n,
        "S_nvpax_mean": 100 * s_nv.mean(),
        "S_nvpax_std": 100 * s_nv.std(),
        "S_nvpax_min": 100 * s_nv.min(),
        "S_nvpax_max": 100 * s_nv.max(),
        # per-step percentiles: the mean hides tail steps where satisfaction
        # dips (brown spikes in the trace), so the floor gates the p50 too
        "S_nvpax_p50": 100 * float(np.percentile(s_nv, 50)),
        "S_nvpax_p99": 100 * float(np.percentile(s_nv, 99)),
        "S_nvpax_p1": 100 * float(np.percentile(s_nv, 1)),
        "flight_steps": len(rows),
        "S_static_mean": 100 * s_st.mean(),
        "S_greedy_mean": 100 * s_gr.mean(),
        "dU_static_mean_pct": float(np.mean(du_st)),
        "dU_greedy_mean_pct": float(np.mean(du_gr)),
        "wall_ms_mean": float(np.mean(wall_warm)),
        "wall_ms_p99": float(np.percentile(wall_warm, 99)),
        "wall_ms_std": float(np.std(wall_warm)),
        "paper": dict(PAPER),
        # acceptance flags (check_bench enforces every meets_*):
        # the paper's per-timestamp dominance claim and the Greedy tie
        "meets_S_ge_static_every_step": bool((s_nv >= s_st - 1e-9).all()),
        "meets_S_ge_greedy": bool(100 * (s_nv.mean() - s_gr.mean()) >= -0.5),
    }
    return out


def main() -> None:
    use_compile_cache()
    import argparse
    import json
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="CI-sized fleet, few steps (bench-smoke job)",
    )
    ap.add_argument(
        "--full",
        action="store_true",
        help="paper geometry over the dense 3-day trace",
    )
    ap.add_argument("--out", default="artifacts/bench")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    flight_path = os.path.join(args.out, "FLIGHT_trace.jsonl")
    if args.smoke:
        res = run(steps=12, stride=96, smoke=True, flight_out=flight_path)
    elif args.full:
        res = run(steps=120, stride=24, flight_out=flight_path)
    else:
        res = run(flight_out=flight_path)

    path = os.path.join(args.out, "BENCH_trace.json")
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    print(
        f"n={res['n_devices']} steps={res['steps']}: S nvPAX "
        f"{res['S_nvpax_mean']:.2f}% / static {res['S_static_mean']:.2f}% / "
        f"greedy {res['S_greedy_mean']:.2f}% "
        f"(paper {PAPER['S_nvpax_mean']}/{PAPER['S_static_mean']}/"
        f"{PAPER['S_greedy_mean']}); p50/p99 {res['S_nvpax_p50']:.2f}/"
        f"{res['S_nvpax_p99']:.2f}%; wall {res['wall_ms_mean']:.1f}ms "
        f"(paper {PAPER['wall_ms_mean']}); wrote {path} + {flight_path}"
    )


if __name__ == "__main__":
    main()
