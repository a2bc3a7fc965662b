"""Solver-internals microbench (§Perf evidence): per-phase iterations and
wall time, warm vs cold starts, waterfill fast-path vs iterated LP, batched
(vmap-over-scenarios) vs sequential throughput, and the degenerate-geometry
certification suite (``run_degenerate`` -> ``BENCH_solver.json``, gated by
``benchmarks/check_bench.py``).

    PYTHONPATH=src python benchmarks/solver_bench.py --degenerate \
        [--out artifacts/bench]
"""

from __future__ import annotations

import time

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.batched import optimize_batched
from repro.core.nvpax import NvpaxOptions, optimize
from repro.core.problem import AllocProblem
from repro.pdn.telemetry import TelemetrySim, TraceConfig
from repro.pdn.tree import build_datacenter

# ISSUE 5 acceptance bound: every degenerate max-min round must exit with a
# certificate (KKT or no-progress/vertex) within this many PDHG iterations
CERT_BUDGET = 5_000


def bench_batched(K: int = 16, level_sizes=(2, 4, 4), gpus: int = 8) -> dict:
    """Batched engine (one vmapped program) vs a sequential optimize() loop
    over the same K scenarios — the MPC / what-if sweep workload."""
    from repro.pdn.tree import build_from_level_sizes

    pdn = build_from_level_sizes(list(level_sizes), gpus_per_server=gpus)
    rng = np.random.default_rng(7)
    reqs = rng.uniform(100, 650, (K, pdn.n))
    aps = [AllocProblem.build(pdn, r) for r in reqs]

    # compile both paths first (one-time cost, amortized per control step)
    optimize(aps[0])
    optimize_batched(aps)

    t0 = time.perf_counter()
    seq = [optimize(ap) for ap in aps]
    seq_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    res_b = optimize_batched(aps)
    bat_s = time.perf_counter() - t0

    max_dev = float(
        max(
            np.abs(seq[k].allocation - res_b.allocation[k]).max()
            for k in range(K)
        )
    )
    return {
        "K": K,
        "n_devices": pdn.n,
        "sequential_s": seq_s,
        "batched_s": bat_s,
        "sequential_solves_per_s": K / seq_s,
        "batched_solves_per_s": K / bat_s,
        "batched_speedup": seq_s / bat_s,
        "batched_seq_max_dev_W": max_dev,
    }


def run(steps: int = 5) -> dict:
    pdn = build_datacenter()
    sim = TelemetrySim(TraceConfig(n_devices=pdn.n, seed=0))

    # compile
    res = optimize(AllocProblem.build(pdn, sim.power(0)))

    cold_ms, warm_ms, cold_it, warm_it = [], [], [], []
    warm = res.warm_state
    for t in range(1, steps + 1):
        ap = AllocProblem.build(pdn, sim.power(t))
        t0 = time.perf_counter()
        rc = optimize(ap)
        cold_ms.append(1000 * (time.perf_counter() - t0))
        cold_it.append(rc.stats["total_iterations"])
        t0 = time.perf_counter()
        rw = optimize(ap, warm=warm)
        warm_ms.append(1000 * (time.perf_counter() - t0))
        warm_it.append(rw.stats["total_iterations"])
        warm = rw.warm_state

    # waterfill fast path vs iterated LP (phases II/III), small surplus step
    from repro.pdn.tree import build_from_level_sizes

    pdn2 = build_from_level_sizes([2, 4, 4], gpus_per_server=8)
    req = np.random.default_rng(0).uniform(150, 450, pdn2.n)
    ap2 = AllocProblem.build(pdn2, req)
    optimize(ap2, NvpaxOptions(use_waterfill=False))  # compile
    t0 = time.perf_counter()
    r_lp = optimize(ap2, NvpaxOptions(use_waterfill=False))
    lp_ms = 1000 * (time.perf_counter() - t0)
    t0 = time.perf_counter()
    r_wf = optimize(ap2, NvpaxOptions(use_waterfill=True))
    wf_ms = 1000 * (time.perf_counter() - t0)
    agree = float(np.abs(r_lp.allocation - r_wf.allocation).max())

    return {
        "n_devices": pdn.n,
        "cold_ms_mean": float(np.mean(cold_ms)),
        "warm_ms_mean": float(np.mean(warm_ms)),
        "cold_iters_mean": float(np.mean(cold_it)),
        "warm_iters_mean": float(np.mean(warm_it)),
        "warm_speedup": float(np.mean(cold_ms) / np.mean(warm_ms)),
        "maxmin_lp_ms": lp_ms,
        "maxmin_waterfill_ms": wf_ms,
        "waterfill_speedup": lp_ms / wf_ms,
        "waterfill_lp_max_dev_W": agree,
        "batched": bench_batched(),
    }


def run_degenerate(n_seeds: int = 2) -> dict:
    """Degenerate-geometry certification suite -> ``BENCH_solver.json``.

    The geometries that stalled the pre-overhaul solver for 50k iterations:
    node caps exactly equal to subtree maxima (oversubscription 1.0) with
    tenant SLA rows, plus an exactly-tied-requests variant.  For each case
    the Phase II max-min LP is solved directly (certified-iteration counts,
    restart counts, optimum quality vs HiGHS when scipy is present) and the
    full three-phase engine step is timed.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import phases, solver
    from repro.core.engine import AllocEngine
    from repro.core.refsolve import HAVE_SCIPY, ref_solve
    from repro.pdn.tenants import assign_tenants
    from repro.pdn.tree import build_from_level_sizes

    cases = []
    with jax.enable_x64(True):
        for seed in range(n_seeds):
            for ties in (False, True):
                pdn = build_from_level_sizes(
                    [2, 2], gpus_per_server=4, oversubscription=1.0
                )
                lay = assign_tenants(
                    pdn, n_tenants=2, devices_per_tenant=4,
                    hi_frac=1.0 if ties else 0.8, seed=seed,
                )
                tele = (
                    np.full(pdn.n, 660.0)
                    if ties
                    else np.random.default_rng(seed).uniform(600, 690, pdn.n)
                )
                ap = AllocProblem.build(
                    pdn, tele, sla=lay.sla_topo(), priority=lay.priority
                )
                p1 = optimize(ap, NvpaxOptions(run_phase2=False, run_phase3=False))
                x1, state = jnp.asarray(p1.phase1), p1.warm_state.p1
                mask_a = ap.active & ~phases.saturated_mask(x1, ap, ap.active)
                prob = phases.lp_step(
                    ap, x1, mask_a, ~(mask_a | ap.idle), ap.idle, 1e-5
                )
                warm = solver.SolverState(
                    x1, jnp.zeros(()), state.y_tree, state.y_sla, state.y_imp
                )
                st, stats = solver.solve(prob, ap.tree, ap.sla, warm)
                case = {
                    "seed": seed,
                    "ties": ties,
                    "iterations": int(stats.iterations),
                    "converged": bool(stats.converged),
                    "kkt_certified": bool(stats.certified),
                    "restarts": int(stats.restarts),
                }
                if HAVE_SCIPY:
                    zref = ref_solve(prob, ap.tree, ap.sla)
                    case["t_err_W"] = abs(float(st.t) - float(zref[-1]))
                    case["x_err_W"] = float(
                        np.abs(np.asarray(st.x) - zref[: ap.n]).max()
                    )

                eng = AllocEngine(pdn, sla=lay.sla_topo(), priority=lay.priority)
                eng.step(tele)
                eng.step(tele)  # prime warm variant
                t0 = time.perf_counter()
                r = eng.step(tele)
                case["engine_step_ms"] = 1000 * (time.perf_counter() - t0)
                case["engine_iterations"] = r.stats["total_iterations"]
                case["engine_converged"] = r.stats["converged"]
                cases.append(case)

    max_iters = max(c["iterations"] for c in cases)
    out = {
        "cert_budget": CERT_BUDGET,
        "cases": cases,
        "max_iterations": max_iters,
        "engine_step_ms_mean": float(
            np.mean([c["engine_step_ms"] for c in cases])
        ),
        "meets_cert_budget": bool(
            all(c["converged"] for c in cases) and max_iters <= CERT_BUDGET
        ),
        "meets_engine_converged": bool(
            all(c["engine_converged"] for c in cases)
        ),
    }
    # only emit the quality flag when the HiGHS reference actually ran —
    # a vacuous True would green-light CI with zero comparisons performed
    if HAVE_SCIPY:
        out["meets_optimum_quality"] = bool(
            all(
                c["t_err_W"] <= 1e-2 and c["x_err_W"] <= 1e-3 for c in cases
            )
        )
    return out


def main() -> None:
    use_compile_cache()
    import argparse
    import json
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--degenerate", action="store_true",
        help="run only the degenerate certification suite and write "
        "BENCH_solver.json (the CI bench-smoke job)",
    )
    ap.add_argument("--out", default="artifacts/bench")
    args = ap.parse_args()

    if args.degenerate:
        res = run_degenerate()
        os.makedirs(args.out, exist_ok=True)
        path = os.path.join(args.out, "BENCH_solver.json")
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        print(
            f"degenerate suite: {len(res['cases'])} cases, max "
            f"{res['max_iterations']} iters (budget {res['cert_budget']}), "
            f"engine step {res['engine_step_ms_mean']:.1f}ms, "
            f"meets_cert_budget={res['meets_cert_budget']}"
        )
        print(f"wrote {path}")
    else:
        print(json.dumps(run(), indent=1))


if __name__ == "__main__":
    main()
