"""Paper Appendix B: tenant SLAs (100 tenants x 100 GPUs, bounds 40-80% of
aggregate max) + random priorities {1,2,3} on the full datacenter trace.

Paper: global S 98.93%, per-tenant S 99.24%, mean lower-SLA margin 54.44%,
worst-tenant margin avg 33.80%, ZERO violations, wall 718.83 ms."""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.core.metrics import satisfaction_ratio, sla_margin, tenant_satisfaction
from repro.core.nvpax import optimize
from repro.core.problem import AllocProblem
from repro.core.treeops import sla_matvec
from repro.pdn.telemetry import TelemetrySim, TraceConfig
from repro.pdn.tenants import appendix_b_layout
from repro.pdn.tree import build_datacenter


def run(steps: int = 6, stride: int = 480, seed: int = 0) -> dict:
    pdn = build_datacenter()
    lay = appendix_b_layout(pdn, seed=seed)
    sim = TelemetrySim(TraceConfig(n_devices=pdn.n, seed=seed))
    sla = lay.sla_topo()
    warm = None
    S, St, marg_mean, marg_min, wall = [], [], [], [], []
    viol = 0
    for i in range(steps):
        power = sim.power(i * stride)
        ap = AllocProblem.build(pdn, power, sla=sla, priority=lay.priority)
        res = optimize(ap, warm=warm)
        warm = res.warm_state
        a = res.allocation
        r = np.asarray(ap.r)
        S.append(satisfaction_ratio(r, a))
        St.append(
            tenant_satisfaction(r, a, lay.tenant_of, lay.n_tenants).mean()
        )
        m = sla_margin(a, lay.tenant_of, lay.n_tenants, lay.b_min, lay.b_max)
        marg_mean.append(m.mean())
        marg_min.append(m.min())
        sums = np.asarray(sla_matvec(jnp.asarray(a), ap.sla))
        viol += int((sums < lay.b_min - 1e-4).sum())
        viol += int((sums > lay.b_max + 1e-4).sum())
        wall.append(res.wall_time_s * 1000)
    return {
        "steps": steps,
        "S_global_mean": 100 * float(np.mean(S)),
        "S_tenant_mean": 100 * float(np.mean(St)),
        "sla_margin_mean": 100 * float(np.mean(marg_mean)),
        "sla_margin_worst_tenant_mean": 100 * float(np.mean(marg_min)),
        "violations": viol,
        "wall_ms_mean": float(np.mean(wall[1:])) if steps > 1 else wall[0],
        "paper": {
            "S_global_mean": 98.93, "S_tenant_mean": 99.24,
            "sla_margin_mean": 54.44, "sla_margin_worst_tenant_mean": 33.80,
            "violations": 0, "wall_ms_mean": 718.83,
        },
    }


if __name__ == "__main__":
    use_compile_cache()
    import json

    print(json.dumps(run(), indent=1))
