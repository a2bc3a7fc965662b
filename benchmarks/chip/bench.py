"""Chip benchmark of the nvPAX control loop: one controller, back to back.

    python3 benchmarks/chip/bench.py --workload hall12k.diurnal --seed 7 \\
        --seconds 30 --trace 0

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
traffic mix.  Everything that belongs to one of them lives in files found
by name: ``configs/<config>.json`` (sizes, precision, guarantees, limits),
``systems/<system>.py`` (how the program under test is built from a
configuration), ``mixes/<mix>.json`` (parameters, read by the generator
the mix names: ``generators/<generator>.py``) and ``metrics/<metric>.py``
(one reader per per-layer metric).

A run builds the deployment, generates the mix's ring of telemetry from
``--seed``, warms up both step programs (the cold one and the warm-carry
one), then drives the control loop for ``--seconds``: telemetry as host
numpy into the system's ``step``, caps back as host numpy.  With
``--trace 1`` the profiler records ``TRACED`` single intervals spread over
the ring, and the window lasts until each was served.  After the window
it compares a sample of the answers, drawn from the seed, with the plain
reference (``checks.py``).  The last stdout line is one JSON object; the
numbers compared are the last lines of stderr.  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 1.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]
# the persistent compile cache: where the environment names one, else at
# one fixed path inside the checkout
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
# the TPU runtime's own log files would go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

import checks  # noqa: E402

SAMPLE = 16  # answers compared with the reference, drawn from the seed
RUNS = HERE / ".runs"  # traces
# the profiler records every device operation (~0.8M a second on one chip;
# writing out 8 s of them took ~180 s), so a traced run records single
# intervals, one profiler session each
TRACED = 8


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` beside this file, imported by path."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Run:
    """What one run measured: the readers in ``metrics/`` take theirs from
    here."""

    def __init__(self):
        self.setup_s = 0.0
        self.compile_s = 0.0
        self.window_s = 0.0
        self.durations: list[float] = []
        self.pdhg_iters: list[int] = []
        self.failed = 0
        self.window_compiles = 0
        self.satisfaction: list[float] = []
        self.compared: dict[str, float] = {}
        self.trace: dict | None = None
        self.traced: dict[int, float] = {}  # ring position -> host seconds
        self.trace_parts: dict[int, dict | None] = {}  # ring position -> reduction
        self.memory_peak_bytes = 0


def run(cfg: dict, mix: dict, seed: int, seconds: float, *, traced: bool = False,
        wrap_step=None) -> Run:
    """One run of a cell.  ``wrap_step`` (tests) wraps the system's step."""
    import devtrace
    import jax
    from compile_log import CompileLog
    from repro.compile_cache import use_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    use_compile_cache()
    log = CompileLog()
    out = Run()
    n = int(np.prod(cfg["fanout"]))
    ring = load_module("generators", mix["generator"]).replay(mix, n, seed)
    step = load_module("systems", cfg["system"]).build(cfg)
    if wrap_step is not None:
        step = wrap_step(step)
    for tele in ring[:2]:  # the cold program, then the warm-carry one
        step(tele)
    out.compile_s = log.seconds
    compiles_before = log.compiles

    pick = random.Random(seed)
    sample: list[tuple[int, object]] = []  # reservoir over the window
    latest: dict[int, np.ndarray] = {}  # each ring position's last caps
    trace_dir = RUNS / "trace"
    pending = trace_positions(ring.shape[0]) if traced else set()
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
    out.setup_s = time.perf_counter() - T_START
    i, t_open = 0, time.perf_counter()
    while True:
        pos = (2 + i) % ring.shape[0]
        tracing = pos in pending
        if tracing:
            jax.profiler.start_trace(str(trace_dir / str(pos)))
        t0 = time.perf_counter()
        try:
            if tracing:
                with jax.profiler.TraceAnnotation(devtrace.SPAN):
                    ans = step(ring[pos])
            else:
                ans = step(ring[pos])
        except Exception as e:  # a failed interval: counted, loop goes on
            print(f"interval {i} failed: {e!r}", file=sys.stderr)
            out.failed += 1
            ans = None
        t1 = time.perf_counter()
        out.durations.append(t1 - t0)
        if tracing:
            jax.profiler.stop_trace()
            pending.discard(pos)
            out.traced[pos] = t1 - t0
        if ans is not None:
            out.pdhg_iters.append(ans.pdhg_iters)
            latest[pos] = ans.allocation
            if len(sample) < SAMPLE:
                sample.append((pos, ans))
            elif (j := pick.randrange(i + 1)) < SAMPLE:
                sample[j] = (pos, ans)
        i += 1
        if t1 - t_open >= seconds and not pending:
            break
    out.window_s = t1 - t_open
    out.window_compiles = log.compiles - compiles_before
    out.memory_peak_bytes = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()
    )
    del step, ans
    gc.collect()
    if traced:
        out.trace_parts = {
            p: devtrace.reduce(devtrace.load(trace_dir / str(p))) for p in out.traced
        }
        out.trace = devtrace.combine(list(out.trace_parts.values()))
        shutil.rmtree(trace_dir, ignore_errors=True)
    out.satisfaction = [
        checks.satisfaction(checks.shaped_requests(cfg, ring[p]), a)
        for p, a in sorted(latest.items())
    ]
    out.compared = checks.compare(cfg, [(ring[p], a) for p, a in sample])
    return out


def trace_positions(cycle: int) -> set[int]:
    """``TRACED`` ring positions, one in each ``TRACED``-th of the ring, each
    at another offset within its part: so the traced intervals cover the
    ring's placements and the intervals after a jump and far from one."""
    k = min(TRACED, cycle)
    stride = cycle // k
    return {j * stride + j % stride for j in range(k)}


def is_correct(r: Run, limits: dict[str, float]) -> bool:
    """Every interval answered, nothing compiled in the window, and every
    number compared within its limit."""
    return (
        r.failed == 0
        and r.window_compiles == 0
        and checks.verdict(r.compared, limits)
    )


def end_to_end(r: Run) -> dict[str, float]:
    d = np.asarray(r.durations)
    return {
        "setup_s": r.setup_s,
        "interval_ms": 1e3 * r.window_s / d.size,
        "interval_p95_ms": 1e3 * float(np.percentile(d, 95)),
        "satisfaction_pct": 100.0 * float(np.mean(r.satisfaction)),
    }


def for_cell(metrics: list[dict], cell: str) -> list[dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"no workload {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    (entry,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    cfg = json.loads((ROOT / entry["file"]).read_text())
    mix = json.loads((HERE / "mixes" / f"{cell['traffic']}.json").read_text())

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(
            f"needs {cell['chips']} TPU chip(s); JAX has {len(devices)} "
            f"{devices[0].platform} device(s)",
            file=sys.stderr,
        )
        return 1

    r = run(cfg, mix, args.seed, args.seconds, traced=bool(args.trace))
    print(
        f"{len(r.durations)} intervals in {r.window_s:.3f} s; compiles in the "
        f"window: {r.window_compiles}; compile_s {r.compile_s:.3f}",
        file=sys.stderr,
    )
    for p, part in sorted(r.trace_parts.items()):
        dev_ms = "none" if part is None else f"{part['device_ms']:.3f}"
        print(
            f"traced ring position {p}: host {1e3 * r.traced[p]:.3f} ms, "
            f"device {dev_ms} ms",
            file=sys.stderr,
        )
    metrics = {}
    if args.trace:
        for spec in for_cell(bench["per_layer"], args.workload):
            value = load_module("metrics", spec["name"]).read(r)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    else:
        e2e = end_to_end(r)
        for spec in for_cell(bench["end_to_end"], args.workload):
            metrics[spec["name"]] = {"value": e2e[spec["name"]], "unit": spec["unit"]}
    limits = cfg["limits"]
    correct = is_correct(r, limits)
    dev = devices[0]
    result = {
        "correct": correct,
        "attempted": len(r.durations),
        "failed": r.failed,
        "metrics": metrics,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
            "memory_peak_bytes": r.memory_peak_bytes,
        },
    }
    if args.trace and r.trace is not None:
        result["device"]["busy_s"] = r.trace["busy_s"]
        result["device"]["window_s"] = r.trace["window_s"]
        result["breakdown"] = {
            "device_ops": [list(x) for x in r.trace["device_ops"]],
            "idle_gaps": [list(x) for x in r.trace["idle_gaps"]],
        }
    # an answer that was not finite reads inf, which JSON cannot hold
    result["compared"] = {
        k: {"value": v if np.isfinite(v) else "inf", "limit": limits[k]}
        for k, v in r.compared.items()
    }
    for k, v in r.compared.items():
        ok = "ok" if v <= limits[k] else "FAIL"
        print(f"compared {k} {v!r} limit {limits[k]!r} {ok}", file=sys.stderr)
    print(json.dumps(result, default=_plain))
    return 0


def _plain(x):
    if isinstance(x, (np.floating, np.integer)):
        return x.item()
    raise TypeError(type(x))


if __name__ == "__main__":
    sys.exit(main())
