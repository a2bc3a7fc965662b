"""Inside the control interval of a cell, on the chip: host time by span,
device time by named scope, waterfill rounds, and what the spans cost.

    python3 benchmarks/chip/step_profile.py --workload hall12k.diurnal \\
        --seed 7 --seconds 30 --pairs 2

The deployment is built as ``systems/engine.py`` builds it, but the engine
is kept, for its stats and its step program's text.  The ring is driven
back to back as ``bench.py`` drives it: warm-up on positions 0 and 1, the
window from position 2.  Each window starts alike (warm state dropped,
positions 0 and 1 again), so windows with spans off and on can be compared
on one seed: ``--pairs`` pairs of ``--seconds`` each, off first.  A window
gives ``interval_ms`` (its length over intervals completed, as
``bench.py``) and, with spans on, ``host_ms`` (``scopes.host_ms``) and the
waterfill rounds per interval.  Then one pass of the ring with spans on
(its mean waterfill rounds per interval) traces ``bench.TRACED`` positions,
one profiler session each, as ``bench.py``'s traced run does.  Per
position: the host wall, each span, ``device_ms``, each scope's device time
(``scopes.scope_ms``), the rounds, and the longest idle gaps, each named by
the innermost engine span open in it (``scopes.program_spans``).  One JSON
object on stdout, per-position lines on stderr.  Without a TPU it prints no
result and exits 1.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench  # noqa: E402  (sets the harness's paths and environment)
import devtrace  # noqa: E402
import numpy as np  # noqa: E402
import scopes  # noqa: E402

SPANS = ("prepare", "upload", "dispatch", "wait", "fetch", "stats")
SCOPES = ("phase1", "phase2", "phase3", "pdhg", "waterfill", "repair", "certify")


def build_engine(cfg: dict):
    import jax.numpy as jnp
    from deploy import uniform_pdn
    from repro.core.engine import AllocEngine
    from repro.core.nvpax import NvpaxOptions

    f64 = cfg["precision"] == "float64"
    return AllocEngine(
        uniform_pdn(cfg),
        options=NvpaxOptions(x64=f64),
        idle_threshold=cfg["idle_threshold"],
        dtype=jnp.float64 if f64 else jnp.float32,
    )


def restart(eng, ring) -> None:
    """The state ``bench.py``'s window starts from."""
    eng.reset_warm()
    for tele in ring[:2]:
        eng.step(tele)


def step_text(eng, tele) -> str:
    """The compiled text of the program that ``eng.step(tele)`` runs."""
    from repro.core import engine as engine_mod

    real, seen = engine_mod._engine_step_jit, []

    def spy(*args, **kwargs):
        seen.append((args, kwargs))
        return real(*args, **kwargs)

    engine_mod._engine_step_jit = spy
    try:
        eng.step(tele)
    finally:
        engine_mod._engine_step_jit = real
    args, kwargs = seen[0]
    with eng._ctx():
        return real.lower(*args, **kwargs).compile().as_text()


def window(eng, ring, seconds: float, with_spans: bool) -> dict:
    from repro.obs import spans

    restart(eng, ring)
    spans.reset()
    if with_spans:
        spans.enable()
    rounds, iters = [], []
    i, t_open = 0, time.perf_counter()
    try:
        while True:
            res = eng.step(ring[(2 + i) % ring.shape[0]])
            rounds.append(sum(res.stats["waterfill_rounds"]))
            iters.append(sum(res.stats["phase_iterations"]))
            i += 1
            if (t1 := time.perf_counter()) - t_open >= seconds:
                break
    finally:
        spans.disable()
    out = {
        "spans": with_spans,
        "intervals": i,
        "interval_ms": 1e3 * (t1 - t_open) / i,
        "waterfill_rounds": float(np.mean(rounds)),
        "pdhg_iters": float(np.mean(iters)),
    }
    if with_spans:
        host = scopes.host_ms(spans.drain())
        out["host_ms"] = float(np.mean(host))
        out["host_ms_p95"] = float(np.percentile(host, 95))
    return out


def traced_pass(eng, ring, names: dict, trace_dir: Path) -> dict:
    import jax
    from repro.obs import spans

    cycle = ring.shape[0]
    pending = bench.trace_positions(cycle)
    restart(eng, ring)
    shutil.rmtree(trace_dir, ignore_errors=True)
    out: dict[int, dict] = {}
    rounds = []
    spans.enable()
    try:
        for i in range(cycle):
            pos = (2 + i) % cycle
            if pos not in pending:
                res = eng.step(ring[pos])
                rounds.append(sum(res.stats["waterfill_rounds"]))
                continue
            spans.reset()
            jax.profiler.start_trace(str(trace_dir / str(pos)))
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(devtrace.SPAN):
                res = eng.step(ring[pos])
            wall = time.perf_counter() - t0
            jax.profiler.stop_trace()
            rounds.append(sum(res.stats["waterfill_rounds"]))
            recs = {r["span"]: r["ms"] for r in spans.drain()}
            out[pos] = {
                "wall_ms": 1e3 * wall,
                "step_ms": recs["engine.step"],
                **{s: recs[f"engine.step/engine.{s}"] for s in SPANS},
                "waterfill_rounds": res.stats["waterfill_rounds"],
                "pdhg_iters": res.stats["phase_iterations"],
            }
    finally:
        spans.disable()
    for pos, row in out.items():
        tr = devtrace.load(trace_dir / str(pos))
        red = devtrace.reduce(scopes.program_spans(tr))
        row["device_ms"] = None if red is None else red["device_ms"]
        row["idle_gaps"] = [] if red is None else red["idle_gaps"][:4]
        row["matched_share"] = scopes.matched_share(tr, names)
        for s in SCOPES:
            row[f"{s}_ms"] = scopes.scope_ms(tr, names, s)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return {"positions": out, "waterfill_rounds": float(np.mean(rounds))}


def run(cfg: dict, mix: dict, seed: int, seconds: float, pairs: int) -> dict:
    import jax
    from repro.compile_cache import use_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    use_compile_cache()
    n = int(np.prod(cfg["fanout"]))
    ring = bench.load_module("generators", mix["generator"]).replay(mix, n, seed)
    eng = build_engine(cfg)
    restart(eng, ring)
    names = scopes.op_names(step_text(eng, ring[2]))
    windows = [
        window(eng, ring, seconds, with_spans)
        for _ in range(pairs)
        for with_spans in (False, True)
    ]
    traced = traced_pass(eng, ring, names, bench.RUNS / "step_profile")
    return {"windows": windows, "traced": traced}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--pairs", type=int, default=2)
    args = ap.parse_args(argv)

    spec = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    (cell,) = [w for w in spec["workloads"] if w["name"] == args.workload]
    (entry,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    cfg = json.loads((bench.ROOT / entry["file"]).read_text())
    mix = json.loads((bench.HERE / "mixes" / f"{cell['traffic']}.json").read_text())

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU chip; JAX has {dev.platform}", file=sys.stderr)
        return 1
    out = run(cfg, mix, args.seed, args.seconds, args.pairs)
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind}
    out["workload"], out["seed"] = args.workload, args.seed
    for w in out["windows"]:
        print(json.dumps(w), file=sys.stderr)
    for pos, row in sorted(out["traced"]["positions"].items()):
        spans_ms = ", ".join(f"{s} {row[s]:.3f}" for s in SPANS)
        gaps = ", ".join(f"{name} {1e3 * t:.3f}" for name, t in row["idle_gaps"])
        print(
            f"traced ring position {pos}: host {row['wall_ms']:.3f} ms "
            f"(spans {spans_ms}); device {row['device_ms']} ms, waterfill "
            f"{row['waterfill_ms']} ms, pdhg {row['pdhg_ms']} ms; rounds "
            f"{row['waterfill_rounds']}; idle gaps (ms) {gaps}",
            file=sys.stderr,
        )
    print(json.dumps(out, default=bench._plain))
    return 0


if __name__ == "__main__":
    sys.exit(main())
