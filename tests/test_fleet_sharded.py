"""Sharded fleet dispatch (ISSUE 6): shard_map over a ("domains",) mesh.

Acceptance criteria covered here:

* sharded dispatch matches stacked dispatch to <= 1e-6 W per device on an
  SLA fleet with mixed priorities (the coordinator exchange — one psum +
  replicated waterfill — reproduces the host planner's grants);
* supply derates, tenant grant changes and device churn stay
  zero-recompile under shard_map (sharded trace-counter assertions);
* a forced multi-device CPU mesh (XLA_FLAGS=
  --xla_force_host_platform_device_count=8) exercises real cross-shard
  collectives in a subprocess — conftest forbids setting XLA_FLAGS inside
  the suite's own process.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import engine as engine_mod
from repro.core.nvpax import NvpaxOptions
from repro.core.solver import SolverOptions
from repro.fleet import FleetLifecycle, FleetOrchestrator
from repro.fleet import sharded as sharded_mod
from repro.pdn.hierarchy_gen import homogeneous_fleet
from repro.pdn.tenants import TenantLayout

OPTS = NvpaxOptions(
    solver=SolverOptions(eps_abs=1e-11, eps_rel=1e-11, max_iters=20_000)
)


def _mixed_layout(pdn, lo_frac=0.35, hi_frac=0.55):
    """One cross-cut tenant (domains 0/1) + one domain-local tenant, with
    mixed scheduling priorities (the tenant devices are high-priority)."""
    tenant_of = np.full(pdn.n, -1, np.int32)
    tenant_of[[0, 1, 16, 17]] = 0
    tenant_of[[4, 5, 6]] = 1
    b_min = np.zeros(2)
    b_max = np.zeros(2)
    for t in range(2):
        umax = pdn.dev_u[tenant_of == t].sum()
        b_min[t], b_max[t] = lo_frac * umax, hi_frac * umax
    priority = np.where(tenant_of >= 0, 2, 1).astype(np.int32)
    return TenantLayout(tenant_of, 2, b_min, b_max, priority)


def test_sharded_matches_stacked_sla_mixed_priorities():
    """<= 1e-6 W per-device parity over cold + warm-carried steps."""
    pdn = homogeneous_fleet(2, domain_oversub=1.15, root_oversub=1.0)
    lay = _mixed_layout(pdn)
    stacked = FleetOrchestrator(pdn, level=1, tenants=lay, mode="stacked", options=OPTS)
    sharded = FleetOrchestrator(pdn, level=1, tenants=lay, mode="sharded", options=OPTS)
    rng = np.random.default_rng(21)
    for _ in range(3):
        tele = rng.uniform(400, 690, pdn.n)
        rs = stacked.step(tele)
        rh = sharded.step(tele)
        assert np.max(np.abs(rh.allocation - rs.allocation)) <= 1e-6
        np.testing.assert_allclose(rh.grants, rs.grants, atol=1e-6)
        for t in range(lay.n_tenants):
            s = rh.allocation[lay.tenant_of == t].sum()
            assert lay.b_min[t] - 1e-4 <= s <= lay.b_max[t] + 1e-4


def test_sharded_churn_and_grants_zero_retrace():
    """Derates, tenant grant changes and leave/rejoin re-pin traced arrays
    only: the sharded program never retraces after its two warm-up traces
    (cold + warm-carry), and tenant minimums hold throughout."""
    pdn = homogeneous_fleet(2, domain_oversub=1.15, root_oversub=1.0)
    lay = _mixed_layout(pdn, lo_frac=0.4)
    orch = FleetOrchestrator(pdn, level=1, tenants=lay, mode="sharded", options=OPTS)
    life = FleetLifecycle(orch)
    tele = np.random.default_rng(22).uniform(500, 690, pdn.n)
    orch.step(tele)
    orch.step(tele)  # compile cold + warm-carry variants
    s0, e0 = sharded_mod.trace_count(), engine_mod.trace_count()
    orch.set_domain_supply(0, 0.8)
    res = orch.step(tele)
    assert res.allocation[lay.tenant_of == 0].sum() >= lay.b_min[0] - 1e-4
    orch.set_tenant_bounds(0, b_min=0.5 * 2800.0, b_max=0.52 * 2800.0)
    res = orch.step(tele)
    s = res.allocation[lay.tenant_of == 0].sum()
    assert 0.5 * 2800.0 - 1e-4 <= s <= 0.52 * 2800.0 + 1e-4
    orch.set_tenant_bounds(0, b_min=lay.b_min[0], b_max=lay.b_max[0])
    life.device_leave([1, 17])
    res = orch.step(tele)
    np.testing.assert_allclose(res.allocation[[1, 17]], 0.0)
    assert res.allocation[lay.tenant_of == 0].sum() >= lay.b_min[0] - 1e-4
    life.device_join([1, 17])
    res = orch.step(tele)
    assert res.allocation[lay.tenant_of == 0].sum() >= lay.b_min[0] - 1e-4
    assert sharded_mod.trace_count() - s0 == 0  # acceptance: no recompile
    assert engine_mod.trace_count() - e0 == 0


_MULTIDEV_SCRIPT = """
import json
import numpy as np
from repro.fleet import FleetOrchestrator
from repro.fleet import sharded as sharded_mod
from repro.pdn.hierarchy_gen import homogeneous_fleet

pdn = homogeneous_fleet(
    8, racks_per_domain=1, servers_per_rack=2, gpus_per_server=4,
    domain_oversub=0.9, root_oversub=1.0,
)
stacked = FleetOrchestrator(pdn, level=1, mode="stacked")
sharded = FleetOrchestrator(pdn, level=1, mode="sharded")
rng = np.random.default_rng(7)
teles = [rng.uniform(300, 690, pdn.n) for _ in range(4)]
parity = 0.0
for t in range(2):
    rs = stacked.step(teles[t])
    rh = sharded.step(teles[t])
    parity = max(parity, float(np.max(np.abs(rh.allocation - rs.allocation))))
s0 = sharded_mod.trace_count()
for t in range(2, 4):
    rs = stacked.step(teles[t])
    rh = sharded.step(teles[t])
    parity = max(parity, float(np.max(np.abs(rh.allocation - rs.allocation))))
retraces = sharded_mod.trace_count() - s0

# two domains per shard, as on a four-chip mesh of eight halls
pdn2 = homogeneous_fleet(
    16, racks_per_domain=1, servers_per_rack=2, gpus_per_server=4,
    domain_oversub=0.9, root_oversub=1.0,
)
stacked2 = FleetOrchestrator(pdn2, level=1, mode="stacked")
sharded2 = FleetOrchestrator(pdn2, level=1, mode="sharded")
phase_parity = 0.0
for t in range(3):
    tele = rng.uniform(60, 690, pdn2.n)
    rs = stacked2.step(tele)
    rh = sharded2.step(tele)
    for name in ("phase1", "phase2", "allocation"):
        gap = np.max(np.abs(getattr(rh, name) - getattr(rs, name)))
        phase_parity = max(phase_parity, float(gap))
print(json.dumps({
    "mesh_devices": sharded_mod.shard_count(sharded.k),
    "parity_W": parity,
    "retraces_after_warmup": retraces,
    "two_per_shard": {
        "domains": sharded2.k,
        "mesh_devices": sharded_mod.shard_count(sharded2.k),
        "phase_parity_W": phase_parity,
    },
}))
"""


@pytest.fixture(scope="module")
def multidevice():
    """One subprocess on 8 forced host devices runs every multi-shard case."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_forced_multidevice_subprocess(multidevice):
    """The real multi-shard path: 8 forced host devices, one domain per
    shard, cross-shard psum + replicated waterfill.  Parity and the
    zero-recompile contract must hold exactly as on the 1-device mesh."""
    out = multidevice
    assert out["mesh_devices"] == 8  # one domain per mesh device
    assert out["parity_W"] <= 1e-6
    assert out["retraces_after_warmup"] == 0


def test_sharded_two_domains_per_shard_phases_match_stacked(multidevice):
    """Two domains per shard, vmapped in lockstep on each: the per-phase
    caps equal the stacked dispatch's to 1e-6 W."""
    out = multidevice["two_per_shard"]
    assert (out["domains"], out["mesh_devices"]) == (16, 8)
    assert out["phase_parity_W"] <= 1e-6
