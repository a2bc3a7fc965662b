"""Paper Figure 3 / section 5.6 + the sharded-dispatch scaling curve.

Three sections, emitted together as the machine-readable
``BENCH_scaling.json`` consumed by CI's bench-smoke job (``check_bench.py``
validates the schema, the ``meets_*`` flags and the regression floors):

* ``single_solve`` (:func:`run`) — wall-clock of a single ``optimize()``
  call on synthetic random hierarchies, n in 1e3-1e5.  Paper: mean runtime
  scales ~n^1.16 on an M4 Pro with Clarabel/HiGHS; we measure the same
  protocol on the PDHG/waterfill stack (warm-started, post-compile) and
  report the fitted exponent.
* ``batched`` (:func:`run_batched`) — batched-solve throughput over
  scenario count K at fixed fleet size (beyond-paper what-if futures).
* ``dispatch`` (:func:`run_fleet`) — time-per-control-step of the fleet
  orchestrator from n=1k to 100k+ devices for **sharded vs stacked vs
  loop** dispatch, against the paper's 264.69 ms allocation interval.
  The sharded rows shard the K-domain program over however many local
  devices are available (CI forces a multi-device CPU mesh via
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and must match
  stacked allocations to <= 1e-6 W.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python benchmarks/scaling.py [--smoke|--full] \
        [--out artifacts/bench]
"""

from __future__ import annotations

import time

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.nvpax import optimize
from repro.core.problem import AllocProblem
from repro.pdn.hierarchy_gen import random_hierarchy
from repro.pdn.telemetry import TelemetrySim, TraceConfig

PAPER_INTERVAL_MS = 264.69

# fleet geometries for the dispatch curve: n -> (n_domains, racks_per_domain,
# servers_per_rack, gpus_per_server); n = K * racks * servers * gpus
FLEET_GEOMETRIES = {
    1_024: (8, 2, 8, 8),
    4_096: (8, 4, 16, 8),
    25_600: (8, 4, 100, 8),
    102_400: (8, 8, 100, 16),
}


def run_batched(n: int = 512, ks=(1, 4, 16, 64), repeats: int = 3):
    """Batched-solve throughput scaling over scenario count K at fixed fleet
    size: one vmapped program evaluating K what-if futures per control step
    (beyond-paper; the sequential-loop baseline is K repeated optimize()s)."""
    from repro.core.batched import optimize_batched

    pdn = random_hierarchy(int(n), seed=3)
    rng = np.random.default_rng(4)
    rows = []
    for K in ks:
        reqs = rng.uniform(100, 650, (K, pdn.n))
        aps = [AllocProblem.build(pdn, r) for r in reqs]
        optimize_batched(aps)  # compile
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            optimize_batched(aps)
            times.append(time.perf_counter() - t0)
        mean_s = float(np.mean(times))
        rows.append({"K": int(K), "mean_s": mean_s, "solves_per_s": K / mean_s})
    return {"n": int(n), "rows": rows}


def run(sizes=(1_000, 5_000, 10_000, 25_000, 50_000, 100_000), repeats=3):
    rows = []
    for n in sizes:
        pdn = random_hierarchy(int(n), seed=1)
        sim = TelemetrySim(TraceConfig(n_devices=pdn.n, seed=2))
        # compile + warm
        ap = AllocProblem.build(pdn, sim.power(0))
        res = optimize(ap)
        warm = res.warm_state
        times = []
        for r in range(repeats):
            ap = AllocProblem.build(pdn, sim.power(r + 1))
            t0 = time.perf_counter()
            res = optimize(ap, warm=warm)
            times.append(time.perf_counter() - t0)
            warm = res.warm_state
        rows.append(
            {
                "n": int(n),
                "mean_s": float(np.mean(times)),
                "std_s": float(np.std(times)),
            }
        )
    ns = np.array([r["n"] for r in rows], float)
    ts = np.array([r["mean_s"] for r in rows], float)
    slope = np.polyfit(np.log(ns), np.log(ts), 1)[0]
    return {"rows": rows, "fitted_exponent": float(slope), "paper_exponent": 1.16}


def _drift_telemetry(n: int, steps: int, seed: int) -> list[np.ndarray]:
    """Slowly-drifting random-walk telemetry (steady-state control load)."""
    rng = np.random.default_rng(seed)
    base = rng.uniform(150, 650, n)
    out = []
    for _ in range(steps):
        base = np.clip(base + rng.normal(0, 15, n), 60, 690)
        out.append(base.copy())
    return out


def run_fleet(
    sizes=(1_024, 4_096, 25_600, 102_400),
    repeats: int = 3,
    loop_max: int = 4_096,
    seed: int = 0,
):
    """Sharded vs stacked vs loop dispatch time-per-control-step.

    Per size: prime two steps (cold compile + the warm-carry jit variant),
    then time ``repeats`` steps of drifting telemetry per mode on identical
    inputs.  The loop dispatch compiles one engine per domain, which is
    prohibitive at large n, so it is capped at ``loop_max`` devices (rows
    beyond the cap record ``None`` — an explicit gap, not silent truncation).
    """
    import jax

    from repro.fleet import FleetOrchestrator
    from repro.fleet import sharded as sharded_mod
    from repro.pdn.hierarchy_gen import homogeneous_fleet

    rows = []
    for n in sizes:
        k, racks, servers, gpus = FLEET_GEOMETRIES[n]
        pdn = homogeneous_fleet(
            k,
            racks_per_domain=racks,
            servers_per_rack=servers,
            gpus_per_server=gpus,
        )
        assert pdn.n == n, (pdn.n, n)
        teles = _drift_telemetry(n, repeats + 2, seed)
        modes = ["stacked", "sharded"] + (["loop"] if n <= loop_max else [])
        ms_by, alloc_by = {}, {}
        for mode in modes:
            orch = FleetOrchestrator(
                pdn, level=1, coordinator_mode="waterfill", mode=mode
            )
            orch.step(teles[0])
            orch.step(teles[1])  # prime the warm-carry jit variant
            ms, allocs = [], []
            for t in range(2, repeats + 2):
                t0 = time.perf_counter()
                r = orch.step(teles[t])
                ms.append(1000 * (time.perf_counter() - t0))
                allocs.append(r.allocation)
            ms_by[mode] = float(np.mean(ms))
            alloc_by[mode] = allocs
        parity = max(
            float(np.max(np.abs(a - b)))
            for a, b in zip(alloc_by["sharded"], alloc_by["stacked"])
        )
        rows.append(
            {
                "n": int(n),
                "n_domains": int(k),
                "mesh_devices": sharded_mod.shard_count(k),
                "stacked_ms_mean": ms_by["stacked"],
                "sharded_ms_mean": ms_by["sharded"],
                "loop_ms_mean": ms_by.get("loop"),
                "sharded_speedup": ms_by["stacked"] / ms_by["sharded"],
                "sharded_parity_W": parity,
                "vs_paper_interval": ms_by["sharded"] / PAPER_INTERVAL_MS,
            }
        )
    out = {
        "paper_interval_ms": PAPER_INTERVAL_MS,
        "n_local_devices": len(jax.devices()),
        "loop_max_n": int(loop_max),
        "repeats": int(repeats),
        "rows": rows,
        "meets_sharded_parity_1e6": bool(
            all(r["sharded_parity_W"] <= 1e-6 for r in rows)
        ),
    }
    big = [r for r in rows if r["n"] >= 25_000]
    if big:
        out["meets_sharded_beats_stacked_25k"] = bool(
            all(r["sharded_speedup"] >= 1.0 for r in big)
        )
    return out


def run_bench(profile: str = "default"):
    """The full gated artifact: dispatch curve + single-solve curve +
    batched throughput, sized by profile (smoke/default/full)."""
    if profile == "smoke":
        dispatch = run_fleet(sizes=(1_024,), repeats=2)
        single = run(sizes=(1_000, 5_000), repeats=1)
        batched = run_batched(n=256, ks=(1, 4), repeats=2)
    elif profile == "full":
        dispatch = run_fleet(repeats=3)
        single = run(repeats=3)
        batched = run_batched()
    else:
        dispatch = run_fleet(sizes=(1_024, 4_096), repeats=2)
        single = run(sizes=(1_000, 5_000, 10_000, 25_000), repeats=2)
        batched = run_batched(ks=(1, 4, 16), repeats=2)
    return {"dispatch": dispatch, "single_solve": single, "batched": batched}


def main() -> None:
    use_compile_cache()
    import argparse
    import json
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="one small fleet + tiny curves (CI bench-smoke job)",
    )
    ap.add_argument("--full", action="store_true", help="the full n=1k..100k+ curves")
    ap.add_argument("--out", default="artifacts/bench")
    args = ap.parse_args()

    profile = "smoke" if args.smoke else ("full" if args.full else "default")
    res = run_bench(profile)

    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, "BENCH_scaling.json")
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    d = res["dispatch"]
    print(f"devices={d['n_local_devices']} (mesh {d['rows'][0]['mesh_devices']})")
    for r in d["rows"]:
        loop = f"{r['loop_ms_mean']:.1f}" if r["loop_ms_mean"] else "-"
        print(
            f"n={r['n']}: sharded {r['sharded_ms_mean']:.1f}ms vs stacked "
            f"{r['stacked_ms_mean']:.1f}ms vs loop {loop}ms "
            f"(x{r['sharded_speedup']:.2f}, parity {r['sharded_parity_W']:.1e} W, "
            f"{r['vs_paper_interval']:.2f}x paper interval)"
        )
    print(
        f"single-solve exponent n^{res['single_solve']['fitted_exponent']:.2f} "
        f"(paper n^1.16); batched "
        f"{res['batched']['rows'][-1]['solves_per_s']:.1f} solves/s at "
        f"K={res['batched']['rows'][-1]['K']}"
    )
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
