"""Readings that the limits of ``correct`` are set from, at a cell's own size,
in one process:

    python3 benchmarks/chip/readings.py --workload hall12k.diurnal \\
        --precision float64 --seconds 10 --seeds 11 12 13

``--precision float64`` gives the program's readings (the lower ones);
``--precision float32`` runs the program's own float32 path, the control,
whose readings have to fail.  Each seed prints one JSON line with the
numbers compared.  The benchmark's own runs never call this.  Without a TPU
it exits 1.
"""

from __future__ import annotations

import argparse
import json
import sys

import bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--precision", choices=("float64", "float32"), required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    spec = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    (cell,) = [w for w in spec["workloads"] if w["name"] == args.workload]
    (entry,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    cfg = json.loads((bench.ROOT / entry["file"]).read_text())
    cfg["precision"] = args.precision
    mix = json.loads((bench.HERE / "mixes" / f"{cell['traffic']}.json").read_text())

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"needs {cell['chips']} TPU chip(s)", file=sys.stderr)
        return 1
    for seed in args.seeds:
        r = bench.run(cfg, mix, seed, args.seconds)
        row = {
            "workload": args.workload,
            "precision": args.precision,
            "seed": seed,
            "intervals": len(r.durations),
            "interval_ms": bench.end_to_end(r)["interval_ms"],
            "pdhg_iters": sum(r.pdhg_iters) / max(len(r.pdhg_iters), 1),
            "correct": bench.is_correct(r, cfg["limits"]),
            **r.compared,
        }
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
