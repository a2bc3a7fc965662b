"""Trace-driven closed-loop simulation at datacenter scale (paper section 5
in miniature): the full 12k-GPU geometry, a window of 30 s control steps,
nvPAX vs Static vs Greedy, straggler tax, and controller runtime.

    PYTHONPATH=src python examples/datacenter_simulation.py --steps 20
"""

import argparse

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.pdn.tree import build_datacenter
from repro.power.simulator import DatacenterSim


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--devices", type=int, default=None,
                    help="override fleet size (default: paper's >12k)")
    args = ap.parse_args()

    if args.devices:
        from repro.pdn.hierarchy_gen import random_hierarchy

        pdn = random_hierarchy(args.devices, seed=0)
    else:
        pdn = build_datacenter()
    print(f"fleet: {pdn.n} GPUs, oversubscription "
          f"{pdn.oversubscription_ratio():.2f}x")

    sim = DatacenterSim.build(pdn, seed=0)
    out = sim.run(args.steps)

    s = out["S_nvpax"]
    print(
        f"\nnvPAX  satisfaction: mean {100 * s.mean():.2f}%  "
        f"min {100 * s.min():.2f}%  (paper: 98.92 / 96.49)"
    )
    print(f"Static satisfaction: mean {100 * out['S_static'].mean():.2f}%  "
          f"(paper: 81.30)")
    print(f"Greedy satisfaction: mean {100 * out['S_greedy'].mean():.2f}%  "
          f"(paper: 98.92)")
    print(
        f"controller wall time: mean {out['wall_ms'].mean():.0f} ms  "
        f"(paper: 264.69 ms on an M4 Pro)"
    )
    print(f"straggler tax (fleet mean): "
          f"{100 * out['straggler_tax'].mean():.2f}%")


if __name__ == "__main__":
    main()
