"""Ablation (beyond paper): oversubscription factor vs satisfaction.

The paper fixes the per-level oversubscription factor at 0.85; operators
actually choose this number.  This ablation sweeps it and reports the
nvPAX / Greedy / Static satisfaction curves on the same telemetry — the
marginal cost of provisioning less power, and where the global optimizer's
advantage over Greedy appears (tighter networks -> more internal
bottlenecks)."""

from __future__ import annotations

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.greedy import greedy_allocate, static_allocate
from repro.core.metrics import satisfaction_ratio
from repro.core.nvpax import optimize
from repro.core.problem import AllocProblem
from repro.pdn.telemetry import TelemetrySim, TraceConfig
from repro.pdn.tree import build_from_level_sizes


def run(factors=(0.95, 0.85, 0.75, 0.70), steps: int = 4) -> dict:
    rows = []
    for f in factors:
        pdn = build_from_level_sizes(
            [2, 6, 8], gpus_per_server=8, oversubscription=f
        )  # 768 devices
        sim = TelemetrySim(TraceConfig(n_devices=pdn.n, seed=0))
        s_nv, s_gr, s_st = [], [], []
        warm = None
        for t in range(steps):
            power = sim.power(t * 240)
            ap = AllocProblem.build(pdn, power)
            res = optimize(ap, warm=warm)
            warm = res.warm_state
            r = np.asarray(ap.r)
            s_nv.append(satisfaction_ratio(r, res.allocation))
            s_gr.append(satisfaction_ratio(r, greedy_allocate(pdn, power)))
            s_st.append(satisfaction_ratio(r, static_allocate(pdn)))
        rows.append(
            {
                "oversub_factor": f,
                "supply_ratio": 1 / pdn.oversubscription_ratio(),
                "S_nvpax": 100 * float(np.mean(s_nv)),
                "S_greedy": 100 * float(np.mean(s_gr)),
                "S_static": 100 * float(np.mean(s_st)),
            }
        )
    return {"rows": rows}


if __name__ == "__main__":
    use_compile_cache()
    import json

    print(json.dumps(run(), indent=1))
