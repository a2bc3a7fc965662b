"""Quickstart: allocate power across a small oversubscribed datacenter.

Builds a 2-hall PDN, generates one telemetry snapshot, and runs the full
three-phase nvPAX policy, printing the allocation against the requests and
both baselines.  Runs in a few seconds on CPU.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.greedy import greedy_allocate, static_allocate
from repro.core.metrics import satisfaction_ratio
from repro.core.nvpax import optimize
from repro.core.problem import AllocProblem
from repro.pdn.telemetry import TelemetrySim, TraceConfig
from repro.pdn.tree import build_from_level_sizes


def main():
    use_compile_cache()
    # 2 halls x 4 racks x 4 servers x 8 GPUs = 256 devices, oversub 0.85/level
    pdn = build_from_level_sizes([2, 4, 4], gpus_per_server=8)
    print(
        f"fleet: {pdn.n} GPUs, {pdn.m} PDN nodes, "
        f"oversubscription {pdn.oversubscription_ratio():.2f}x "
        f"(root budget {pdn.node_cap[0] / 1e3:.1f} kW)"
    )

    telemetry = TelemetrySim(TraceConfig(n_devices=pdn.n, seed=0)).power(0)
    problem = AllocProblem.build(pdn, telemetry)
    result = optimize(problem)

    r = np.asarray(problem.r)
    a = result.allocation
    print(f"\nrequests: total {r.sum() / 1e3:.1f} kW")
    print(f"nvPAX   : total {a.sum() / 1e3:.1f} kW  "
          f"satisfaction {100 * satisfaction_ratio(r, a):.2f}%")
    for name, base in (
        ("Static", static_allocate(pdn)),
        ("Greedy", greedy_allocate(pdn, telemetry)),
    ):
        print(f"{name:8s}: total {base.sum() / 1e3:.1f} kW  "
              f"satisfaction {100 * satisfaction_ratio(r, base):.2f}%")
    print(f"\nsolver: {result.stats['solves']} convex solves, "
          f"{result.stats['iterations']} PDHG iterations, "
          f"{1000 * result.wall_time_s:.0f} ms wall")


if __name__ == "__main__":
    main()
