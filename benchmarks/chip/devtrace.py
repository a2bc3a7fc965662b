"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes into
plain arrays: per device, the operations of the ``XLA Ops`` line of each
``/device:TPU:<i>`` plane, and the host events of the thread that opened
the benchmark's interval spans.  An event is a name id (into ``names``), a
start and an end, in seconds.  ``reduce`` does the arithmetic on that form
alone, so a small recorded trace checks it (``tests/test_devtrace.py``).

- busy: the union of a device's operation intervals inside the traced
  window (first interval span's start to the last one's end); ``busy_s``
  averages it over the devices.
- per interval: the union of operation time inside each interval span,
  averaged over devices and intervals (``device_ms``).
- collectives: the union of time in collective operations (all-reduce,
  all-gather, reduce-scatter, collective-permute, all-to-all), per interval;
  None where the trace holds none.
- breakdown: the operations that took most device time, mean over devices
  (an operation that holds others, a loop or a conditional, is left out so
  that no time counts twice), and the longest gaps of device 0 with no
  operation running, each named by the innermost host event open at its
  midpoint.

``combine`` adds up the reductions of several traces (a traced run records
one profiler session per traced interval): windows and busy time add,
per-interval times are means over all their intervals.
"""

from __future__ import annotations

import re
from pathlib import Path

import numpy as np

SPAN = "interval"  # the benchmark's host span around each control interval
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
)
TOP = 10


def load(profile_dir: Path) -> dict:
    """Devices' operations and the interval thread's host events of the one
    ``.xplane.pb`` under ``profile_dir``."""
    from jax.profiler import ProfileData

    (path,) = sorted(Path(profile_dir).rglob("*.xplane.pb"))
    data = ProfileData.from_file(str(path))
    devices, host = [], events([])
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices += [events(ln.events) for ln in plane.lines if ln.name == "XLA Ops"]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                ev = events(line.events)
                if SPAN in ev["names"] and ev["id"].size > host["id"].size:
                    host = ev
    return {"devices": devices, "host": host}


def events(evs) -> dict:
    """Profiler events as arrays; an operation's name is the HLO text up to
    ``=`` (``%fusion.12``), the rest is its signature."""
    table: dict[str, int] = {}
    ids, start, dur = [], [], []
    for e in evs:
        ids.append(table.setdefault(e.name, len(table)))
        start.append(e.start_ns)
        dur.append(e.duration_ns)
    s = np.asarray(start, np.float64) * 1e-9
    return {
        "names": [n.split(" = ", 1)[0] for n in table],
        "id": np.asarray(ids, np.int64),
        "start": s,
        "end": s + np.asarray(dur, np.float64) * 1e-9,
    }


def merge(start: np.ndarray, end: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Disjoint, sorted intervals covering the same time as the input."""
    if start.size == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], np.maximum.accumulate(end[order])
    new = np.ones(s.size, bool)  # a block starts after all that came before
    new[1:] = s[1:] > e[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.append(e[idx[1:] - 1], e[-1])


def covered(start, end, a: float, b: float) -> float:
    """Length of ``[a, b]`` covered by disjoint intervals."""
    return float(np.sum(np.clip(np.minimum(end, b) - np.maximum(start, a), 0, None)))


def leaves(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Mask of the events that hold no other event."""
    order = np.lexsort((-end, start))  # a holder sorts just before its first
    s, e = start[order], end[order]
    holds = np.zeros(s.size, bool)
    holds[:-1] = e[:-1] >= e[1:]
    out = np.empty(s.size, bool)
    out[order] = ~holds
    return out


def reduce(tr: dict) -> dict | None:
    """Device numbers of a loaded trace; None when it holds no interval span
    or no device operation."""
    host = tr["host"]
    is_span = np.asarray([n == SPAN for n in host["names"]], bool)[host["id"]]
    spans = list(zip(host["start"][is_span], host["end"][is_span]))
    devices = [d for d in tr["devices"] if d["id"].size]
    if not spans or not devices:
        return None
    w0, w1 = spans[0][0], spans[-1][1]
    busy, per_interval, coll, op_time = [], [], [], {}
    n_coll = 0
    for d in devices:
        ms, me = merge(d["start"], d["end"])
        busy.append(covered(ms, me, w0, w1))
        per_interval.append(np.mean([covered(ms, me, a, b) for a, b in spans]))
        coll_name = [bool(COLLECTIVE.search(n)) for n in d["names"]]
        is_coll = np.asarray(coll_name, bool)[d["id"]]
        n_coll += int(is_coll.sum())
        coll.append(covered(*merge(d["start"][is_coll], d["end"][is_coll]), w0, w1))
        inside = np.clip(np.minimum(d["end"], w1) - np.maximum(d["start"], w0), 0, None)
        leaf = leaves(d["start"], d["end"])
        per_id = np.bincount(d["id"][leaf], inside[leaf], minlength=len(d["names"]))
        for name, t in zip(d["names"], per_id):
            if t > 0:
                op_time[name] = op_time.get(name, 0.0) + float(t) / len(devices)
    ms, me = merge(devices[0]["start"], devices[0]["end"])
    gaps = [
        (max(a, w0), min(b, w1))
        for a, b in zip(np.append(w0, me), np.append(ms, w1))
        if min(b, w1) > max(a, w0)
    ]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": float(w1 - w0),
        "busy_s": float(np.mean(busy)),
        "intervals": len(spans),
        "device_ms": 1e3 * float(np.mean(per_interval)),
        "collective_ms": 1e3 * float(np.mean(coll)) / len(spans) if n_coll else None,
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1]),
        "idle_gaps": [(_label(host, (a + b) / 2), float(b - a)) for a, b in gaps[:TOP]],
    }


def combine(parts: list) -> dict | None:
    """One reduction of several traces' reductions (None where a trace held
    nothing); ``device_ops`` and ``idle_gaps`` keep the ``TOP`` largest."""
    parts = [p for p in parts if p is not None]
    if not parts:
        return None
    n = sum(p["intervals"] for p in parts)
    ops: dict[str, float] = {}
    for p in parts:
        for name, t in p["device_ops"]:
            ops[name] = ops.get(name, 0.0) + t
    coll = [p for p in parts if p["collective_ms"] is not None]
    gaps = sorted((g for p in parts for g in p["idle_gaps"]), key=lambda g: -g[1])
    return {
        "window_s": sum(p["window_s"] for p in parts),
        "busy_s": sum(p["busy_s"] for p in parts),
        "intervals": n,
        "device_ms": sum(p["device_ms"] * p["intervals"] for p in parts) / n,
        "collective_ms": (
            sum(p["collective_ms"] * p["intervals"] for p in coll) / n if coll else None
        ),
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": gaps[:TOP],
    }


def _label(host: dict, t: float) -> str:
    """The innermost host event open at ``t``, or ``"no host event"``."""
    s, e = host["start"], host["end"]
    open_ = np.flatnonzero((s <= t) & (e >= t))
    if open_.size == 0:
        return "no host event"
    return host["names"][host["id"][open_[np.argmin(e[open_] - s[open_])]]]
