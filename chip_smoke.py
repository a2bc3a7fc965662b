"""Smoke run of the served control step on a TPU.

    python chip_smoke.py               # the paper's 12,288-device hall, one chip
    python chip_smoke.py --four-chips  # a 102,400-device fleet sharded over 4 chips

Default run: the paper's production hall (``build_datacenter()``: 4 halls x
24 racks x 16 servers x 8 GPUs, l=200 W, u=700 W, idle below 150 W) is
driven by ``TelemetrySim`` through ``AllocEngine.step`` at the default
``NvpaxOptions`` (float64), one control interval every 48 trace steps.
Every step must give caps inside ``[dev_l, dev_u]``, every subtree sum at
most its cap + 1e-6 W, a converged solve, and satisfaction at or above the
static equal share.  A second ``AllocEngine`` on the host CPU runs the same
steps; total power must agree to 1e-6 W.

``--four-chips``: ``FleetOrchestrator(level=1, mode="sharded")`` on K=8
domains x 8 racks x 100 servers x 16 GPUs over a 4-chip mesh, against
``mode="stacked"`` on one chip.  Allocations must agree to 1e-6 W per
device, and the padded ``[K, ...]`` domain arrays must sit on 4 devices.

Everything runs in this one process, which holds the chip.  Without a TPU
the script exits non-zero and prints no result.  The last line of stdout is
``{"ok": true, "device": {...}}``; any failed check exits non-zero first.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from collections import defaultdict
from pathlib import Path

# the host-CPU reference engine needs the CPU backend next to the TPU
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core.engine import AllocEngine  # noqa: E402
from repro.core.greedy import static_allocate  # noqa: E402
from repro.core.metrics import satisfaction_ratio  # noqa: E402
from repro.fleet import FleetOrchestrator  # noqa: E402
from repro.pdn.hierarchy_gen import homogeneous_fleet  # noqa: E402
from repro.pdn.telemetry import TelemetrySim, TraceConfig  # noqa: E402
from repro.pdn.tree import build_datacenter  # noqa: E402

HALL_STEPS = 8  # control intervals; the first two compile (cold, warm-carry)
FLEET_STEPS = 4
STRIDE = 48  # trace steps between sampled intervals
PARITY_W = 1e-6  # the repo's cross-path bar (README "engine parity")
CAP_SLACK_W = 1e-6

# lowering to StableHLO and the XLA compile, each reported once per jitted
# program (tracing is left out: its events nest, one per inner jnp call)
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileLog:
    """Lower + compile seconds per jitted program, from JAX's own monitoring
    events (a persistent-cache hit shows as a short compile)."""

    def __init__(self):
        self.secs: dict[str, float] = defaultdict(float)
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event in _COMPILE_EVENTS:
            name = str(kw.get("fun_name", "?")).removeprefix("jit(").rstrip(")")
            self.secs[name] += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> tuple[dict[str, float], int]:
        out, hits = dict(self.secs), self.hits
        self.secs.clear()
        self.hits = 0
        return out, hits


class Checks:
    def __init__(self):
        self.failed: list[str] = []

    def expect(self, ok: bool, what: str) -> None:
        if not ok:
            self.failed.append(what)
            print(f"FAIL {what}", flush=True)


def subtree_sums(pdn, alloc: np.ndarray) -> np.ndarray:
    """Exact per-node sums: a prefix-sum difference loses ~1e-6 W to
    rounding at 1e5 devices, which is the size of the bar it checks."""
    return np.array(
        [math.fsum(alloc[s:e]) for s, e in zip(pdn.node_start, pdn.node_end)]
    )


def check_feasible(checks: Checks, pdn, alloc: np.ndarray, tag: str) -> None:
    checks.expect(
        bool((alloc >= pdn.dev_l).all() and (alloc <= pdn.dev_u).all()),
        f"{tag}: caps outside [dev_l, dev_u]",
    )
    over = float(np.max(subtree_sums(pdn, alloc) - pdn.node_cap))
    checks.expect(over <= CAP_SLACK_W, f"{tag}: subtree sum over its cap by {over} W")


def timed_steps(step, teles, log: CompileLog, name: str):
    """Run ``step`` over the telemetry; per step: (result, wall s, compile
    seconds of ``name``, other compile seconds, persistent-cache hits)."""
    out = []
    for tele in teles:
        t0 = time.perf_counter()
        res = jax.block_until_ready(step(tele))  # the result is host numpy
        wall = time.perf_counter() - t0
        secs, hits = log.take()
        mine = secs.pop(name, 0.0)
        out.append((res, wall, mine, sum(secs.values()), hits))
    return out


def report_walls(tag: str, rows) -> None:
    for i, label in enumerate(("cold", "warm-carry")):
        _, wall, mine, other, hits = rows[i]
        print(
            f"{tag} compile {label} program (lower + XLA): {mine:.2f} s "
            f"(other jits {other:.2f} s, cache hits {hits}, step wall {wall:.3f} s)"
        )
    warm = np.array([r[1] for r in rows[2:]]) * 1e3
    print(
        f"{tag} warm step wall: p50 {np.percentile(warm, 50):.3f} ms, "
        f"max {warm.max():.3f} ms, n={warm.size} (smoke reading, not a benchmark)"
    )


def run_hall(checks: Checks, log: CompileLog) -> None:
    pdn = build_datacenter()
    sim = TelemetrySim(TraceConfig(n_devices=pdn.n, seed=0))
    teles = [sim.power(i * STRIDE) for i in range(HALL_STEPS)]
    print(f"hall: n={pdn.n} devices, m={pdn.m} nodes, {HALL_STEPS} steps")

    eng = AllocEngine(pdn)
    rows = timed_steps(eng.step, teles, log, "_engine_solve")
    report_walls("tpu", rows)

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref = AllocEngine(pdn)
        ref_rows = timed_steps(ref.step, teles, log, "_engine_solve")

    a_static = static_allocate(pdn)
    worst_total = worst_dev = 0.0
    for i, (tele, row, ref_row) in enumerate(zip(teles, rows, ref_rows)):
        res, ref_res = row[0], ref_row[0]
        alloc = res.allocation
        check_feasible(checks, pdn, alloc, f"step {i}")
        checks.expect(bool(res.stats["converged"]), f"step {i}: not converged")
        active = tele >= eng.idle_threshold
        r = np.where(active, np.clip(tele, pdn.dev_l, pdn.dev_u), pdn.dev_l)
        s_nv = satisfaction_ratio(r, alloc)
        s_st = satisfaction_ratio(r, a_static)
        checks.expect(s_nv >= s_st - 1e-9, f"step {i}: S {s_nv} below static {s_st}")
        d_total = abs(float(alloc.sum()) - float(ref_res.allocation.sum()))
        devs = [
            float(np.max(np.abs(a - b)))
            for a, b in (
                (res.phase1, ref_res.phase1),
                (res.phase2, ref_res.phase2),
                (alloc, ref_res.allocation),
            )
        ]
        worst_total = max(worst_total, d_total)
        worst_dev = max(worst_dev, devs[2])
        print(
            f"step {i}: PDHG iterations per phase {res.stats['phase_iterations']} "
            f"(cpu {ref_res.stats['phase_iterations']}), S {100 * s_nv:.4f}% "
            f"(static {100 * s_st:.4f}%), total {alloc.sum():.6f} W, "
            f"|tpu-cpu| total {d_total:.3e} W, per device x1/x2/x3 "
            f"{devs[0]:.3e}/{devs[1]:.3e}/{devs[2]:.3e} W"
        )
        checks.expect(
            d_total <= PARITY_W, f"step {i}: total power off cpu by {d_total} W"
        )
    print(
        f"parity vs cpu engine: max |total| {worst_total:.3e} W (bar {PARITY_W}), "
        f"max per device {worst_dev:.3e} W"
    )


def run_fleet(checks: Checks, log: CompileLog) -> None:
    devices = jax.devices()
    checks.expect(len(devices) >= 4, f"needs 4 devices, has {len(devices)}")
    if checks.failed:
        return
    pdn = homogeneous_fleet(
        8, racks_per_domain=8, servers_per_rack=100, gpus_per_server=16
    )
    sim = TelemetrySim(TraceConfig(n_devices=pdn.n, seed=0))
    teles = [sim.power(i * STRIDE) for i in range(FLEET_STEPS)]
    print(f"fleet: n={pdn.n} devices, m={pdn.m} nodes, K=8, {FLEET_STEPS} steps")

    sharded = FleetOrchestrator(pdn, level=1, mode="sharded")
    sh_rows = timed_steps(sharded.step, teles, log, "_step_jit")
    report_walls("sharded", sh_rows)
    # the padded [K, ...] topology arrays live on the mesh, two domains each
    spans = {
        name: len({s.device for s in leaf.addressable_shards})
        for name, leaf in sharded._dom._asdict().items()
    }
    print(f"sharded mesh: {sharded._mesh.devices.size} devices; shards {spans}")
    checks.expect(
        all(v == 4 for v in spans.values()), f"domain arrays not on 4 devices: {spans}"
    )

    with jax.default_device(devices[0]):
        stacked = FleetOrchestrator(pdn, level=1, mode="stacked")
        st_rows = timed_steps(stacked.step, teles, log, "_fleet_solve")
    report_walls("stacked", st_rows)

    worst = 0.0
    for i, (sh, st) in enumerate(zip(sh_rows, st_rows)):
        a_sh, a_st = sh[0].allocation, st[0].allocation
        for tag, res in (("sharded", sh[0]), ("stacked", st[0])):
            check_feasible(checks, pdn, res.allocation, f"{tag} step {i}")
            checks.expect(
                bool(np.all(res.stats["converged"])), f"{tag} step {i}: not converged"
            )
        dev = float(np.max(np.abs(a_sh - a_st)))
        worst = max(worst, dev)
        print(
            f"step {i}: iterations sharded {int(np.sum(sh[0].stats['iterations']))} "
            f"stacked {int(np.sum(st[0].stats['iterations']))}, "
            f"|sharded-stacked| max per device {dev:.3e} W"
        )
        checks.expect(dev <= PARITY_W, f"step {i}: sharded off stacked by {dev} W")
    print(f"parity sharded vs stacked: max per device {worst:.3e} W (bar {PARITY_W})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="run only the sharded-fleet phase on a 4-chip mesh",
    )
    args = ap.parse_args()

    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev0.platform}", file=sys.stderr)
        return 1
    print(f"device_kind: {dev0.device_kind}, devices: {len(jax.devices())}")
    print(f"compile cache: {use_compile_cache()}")
    log = CompileLog()
    checks = Checks()
    t0 = time.perf_counter()
    (run_fleet if args.four_chips else run_hall)(checks, log)
    print(f"total wall {time.perf_counter() - t0:.1f} s")
    if checks.failed:
        print(f"{len(checks.failed)} checks failed", file=sys.stderr)
        return 1
    verdict = {
        "ok": True,
        "device": {
            "platform": dev0.platform,
            "kind": dev0.device_kind,
            "count": len(jax.devices()),
        },
    }
    print(json.dumps(verdict))
    return 0


if __name__ == "__main__":
    sys.exit(main())
