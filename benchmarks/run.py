"""Benchmark harness entry point: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Default sizes finish on a laptop-class CPU in ~10 minutes; ``--full`` runs
the paper-scale versions (3-day trace subsets, 1e5-device scaling)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

from repro.compile_cache import use_compile_cache


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default="artifacts/bench")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    from benchmarks import (
        ablation_oversub,
        engine_bench,
        fleet_bench,
        kernel_bench,
        nonuniform,
        obs_bench,
        roofline,
        satisfaction_trace,
        scaling,
        sla_priorities,
        solver_bench,
    )

    suite = [
        # machine-readable engine perf trajectory (tracked across PRs; also
        # emitted standalone by `python benchmarks/engine_bench.py`)
        (
            "BENCH_engine",
            lambda: engine_bench.run(
                ns=(512, 2048, 12288) if args.full else (512, 2048),
                steps=6 if args.full else 4,
            ),
        ),
        # multi-domain fleet orchestrator: dispatch perf + parity, brownout
        # coordination, churn re-pins (also standalone: fleet_bench.py)
        (
            "BENCH_fleet",
            lambda: fleet_bench.run(
                fleet_bench.GEOMETRIES["full" if args.full else "default"]
            ),
        ),
        ("nonuniform_appendix_a", lambda: nonuniform.run()),
        # Fig 2 satisfaction/runtime comparison on the AllocEngine control
        # loop, emitted under the BENCH_ prefix so check_bench gates it
        # (also standalone: satisfaction_trace.py --smoke/--full)
        (
            "BENCH_trace",
            lambda: satisfaction_trace.run(
                steps=120 if args.full else 24,
                stride=24 if args.full else 96,
            ),
        ),
        # Fig 3 single-solve curve + batched throughput + the sharded vs
        # stacked vs loop dispatch curve, emitted under the BENCH_ prefix so
        # check_bench gates it (schema, parity <= 1e-6 W, regression floors);
        # also standalone: scaling.py --smoke under forced host devices
        (
            "BENCH_scaling",
            lambda: scaling.run_bench("full" if args.full else "default"),
        ),
        # Appendix B tenant-SLA run, emitted under the BENCH_ prefix so the
        # check_bench gate consumes it alongside BENCH_engine/BENCH_fleet
        # (one entry point reproduces every artifact CI checks)
        (
            "BENCH_sla_priorities",
            lambda: sla_priorities.run(steps=8 if args.full else 3),
        ),
        # degenerate-geometry certification suite (ISSUE 5): certified
        # iteration counts on the fixtures that stalled the pre-overhaul
        # solver, gated by check_bench alongside BENCH_engine/BENCH_fleet
        (
            "BENCH_solver",
            lambda: solver_bench.run_degenerate(n_seeds=3 if args.full else 2),
        ),
        # flight-recorder overhead gate (PR 8): recording must add zero
        # retraces and <= 5% warm-step wall on the engine smoke loop
        (
            "BENCH_obs",
            lambda: obs_bench.run(reps=8 if args.full else 6),
        ),
        ("solver_bench", lambda: solver_bench.run(steps=5 if args.full else 3)),
        ("kernel_bench", lambda: kernel_bench.run()),
        ("roofline_summary", lambda: roofline.run()),
        (
            "ablation_oversub",
            lambda: ablation_oversub.run(steps=6 if args.full else 3),
        ),
    ]

    failed = []
    for name, fn in suite:
        t0 = time.time()
        try:
            res = fn()
            status = "ok"
        except Exception as e:  # pragma: no cover
            traceback.print_exc()
            res = {"error": f"{type(e).__name__}: {e}"}
            status = "ERROR"
            failed.append(name)
        dt = time.time() - t0
        with open(os.path.join(args.out, f"{name}.json"), "w") as f:
            json.dump(res, f, indent=1)
        line = f"[{status}] {name} ({dt:.1f}s)"
        headline = {
            "BENCH_engine": lambda r: " | ".join(
                f"n={row['n_devices']}: engine {row['engine_ms_mean']:.1f}ms "
                f"(x{row['engine_speedup']:.1f} vs rebuild, "
                f"dev {row['engine_rebuild_max_dev_W']:.1e} W)"
                for row in r["fleets"]
            ) + f" | 5x@512: {r['meets_5x_at_512']}",
            "BENCH_fleet": lambda r: (
                f"n={r['perf']['n_devices']} K={r['perf']['n_domains']}: "
                f"stacked {r['perf']['fleet_stacked_ms_mean']:.1f}ms vs mono "
                f"{r['perf']['mono_engine_ms_mean']:.1f}ms, parity "
                f"{r['perf']['parity_total_dev_W']:.1e} W | brownout S "
                f"{r['brownout']['S_fleet_mean']:.3f} vs static "
                f"{r['brownout']['S_static_mean']:.3f} | churn retraces "
                f"{r['churn']['fleet_retraces']} | sla parity "
                f"{r['sla']['parity_total_dev_W']:.1e} W, brownout min margin "
                f"{r['sla']['brownout_min_margin_W']['nvpax']:.0f} W "
                f"(static {r['sla']['brownout_min_margin_W']['static']:.0f})"
            ),
            "nonuniform_appendix_a": lambda r: (
                f"S_nvpax={r['S_nvpax']:.2f}% (paper 83.26) "
                f"S_greedy={r['S_greedy']:.2f}% (paper 73.94)"
            ),
            "BENCH_trace": lambda r: (
                f"S: nvPAX {r['S_nvpax_mean']:.2f}% / static "
                f"{r['S_static_mean']:.2f}% / greedy {r['S_greedy_mean']:.2f}% "
                f"(paper 98.92/81.30/98.92); wall {r['wall_ms_mean']:.0f}ms "
                f"(paper 264.69)"
            ),
            "BENCH_scaling": lambda r: (
                f"runtime ~ n^{r['single_solve']['fitted_exponent']:.2f} "
                f"(paper n^1.16) | "
                + " | ".join(
                    f"n={row['n']}: sharded {row['sharded_ms_mean']:.0f}ms "
                    f"(x{row['sharded_speedup']:.2f} vs stacked, "
                    f"parity {row['sharded_parity_W']:.0e} W)"
                    for row in r["dispatch"]["rows"]
                )
            ),
            "BENCH_sla_priorities": lambda r: (
                f"S={r['S_global_mean']:.2f}% margins "
                f"{r['sla_margin_mean']:.1f}%/{r['sla_margin_worst_tenant_mean']:.1f}% "
                f"violations={r['violations']} (paper 98.93/54.4/33.8/0)"
            ),
            "BENCH_obs": lambda r: (
                f"overhead x{r['overhead_ratio']:.3f} "
                f"(bar {r['overhead_bar']}), retraces "
                f"{r['retraces_while_recording']}, "
                f"{r['flight_steps']} flight rows"
            ),
            "BENCH_solver": lambda r: (
                f"{len(r['cases'])} degenerate cases, max {r['max_iterations']} "
                f"iters (budget {r['cert_budget']}), certified="
                f"{r['meets_cert_budget']}"
            ),
            "solver_bench": lambda r: (
                f"warm {r['warm_ms_mean']:.0f}ms vs cold {r['cold_ms_mean']:.0f}ms; "
                f"waterfill x{r['waterfill_speedup']:.1f} vs LP"
            ),
            "kernel_bench": lambda r: (
                f"allclose: pdhg={r['pdhg_update_allclose']} "
                f"tree={r['tree_matvec_allclose']} "
                f"flash={r['flash_attention_allclose']}"
            ),
            "roofline_summary": lambda r: (
                f"{r['cells_ok_pod']} pod + {r['cells_ok_multipod']} multipod "
                f"cells OK; bottlenecks {r['bottleneck_histogram']}"
            ),
            "ablation_oversub": lambda r: " | ".join(
                f"f={row['oversub_factor']}: nv {row['S_nvpax']:.1f} "
                f"gr {row['S_greedy']:.1f} st {row['S_static']:.1f}"
                for row in r["rows"]
            ),
        }
        if status == "ok" and name in headline:
            line += "  " + headline[name](res)
        elif status == "ERROR":
            line += "  " + res["error"]
        print(line, flush=True)
    if failed:
        # every phase still runs and writes its JSON, but the run fails
        sys.exit(f"failed phases: {', '.join(failed)}")


if __name__ == "__main__":
    main()
