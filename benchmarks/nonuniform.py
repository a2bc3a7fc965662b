"""Paper Appendix A / Figure 4: the non-uniform hierarchy where Greedy
proportional allocation loses to the global optimum.

Paper: nvPAX S = 83.26%, Greedy S = 73.94% (gap 9.32 points)."""

from __future__ import annotations

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.greedy import greedy_allocate
from repro.core.metrics import satisfaction_ratio
from repro.core.nvpax import optimize
from repro.core.problem import AllocProblem
from repro.pdn.hierarchy_gen import NONUNIFORM_REQUESTS, nonuniform_example


def run() -> dict:
    pdn = nonuniform_example()
    req = NONUNIFORM_REQUESTS
    r = np.clip(req, pdn.dev_l, pdn.dev_u)
    ap = AllocProblem.build(pdn, req, active=np.ones(pdn.n, bool))
    res = optimize(ap)
    s_nv = 100 * satisfaction_ratio(r, res.allocation)
    s_gr = 100 * satisfaction_ratio(r, greedy_allocate(pdn, req))
    return {
        "S_nvpax": s_nv,
        "S_greedy": s_gr,
        "gap_points": s_nv - s_gr,
        "paper": {"S_nvpax": 83.26, "S_greedy": 73.94, "gap_points": 9.32},
        "converged": bool(res.stats["converged"]),
    }


if __name__ == "__main__":
    use_compile_cache()
    import json

    print(json.dumps(run(), indent=1))
