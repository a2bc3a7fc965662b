"""The trace reduction against numbers worked out by hand, and against a
plain sweep on a recorded TPU v5e trace of the hall, cropped around an
interval boundary (``hall_excerpt.json``).

    python3 -m pytest benchmarks/chip/tests
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import devtrace  # noqa: E402


def as_trace(raw: dict) -> dict:
    def ev(d):
        return {
            "names": list(d["names"]),
            "id": np.asarray(d["id"], np.int64),
            "start": np.asarray(d["start"], np.float64),
            "end": np.asarray(d["end"], np.float64),
        }

    return {"devices": [ev(d) for d in raw["devices"]], "host": ev(raw["host"])}


def test_hand_worked_trace():
    # two intervals [0, 9.5] and [12, 20]; device 0 runs a loop [1, 9] that
    # holds two ops, an op [11, 13] between intervals and an all-reduce
    # [14, 15]; device 1 runs one op [2, 4]
    host = {
        "names": ["interval", "step"],
        "id": [0, 1, 0],
        "start": [0.0, 8.5, 12.0],
        "end": [9.5, 9.5, 20.0],
    }
    dev0 = {
        "names": ["%while.1", "%fusion.2", "%fusion.3", "%copy.4", "%all-reduce.5"],
        "id": [0, 1, 2, 3, 4],
        "start": [1.0, 1.0, 5.0, 11.0, 14.0],
        "end": [9.0, 3.0, 8.0, 13.0, 15.0],
    }
    dev1 = {"names": ["%fusion.9"], "id": [0], "start": [2.0], "end": [4.0]}
    r = devtrace.reduce(as_trace({"devices": [dev0, dev1], "host": host}))
    assert r["window_s"] == 20.0
    assert r["intervals"] == 2
    # device 0 busy [1, 9] + [11, 13] + [14, 15] = 11; device 1: 2
    assert r["busy_s"] == pytest.approx(6.5)
    # per interval: device 0 (8 + 2) / 2 = 5, device 1 (2 + 0) / 2 = 1
    assert r["device_ms"] == pytest.approx(3e3)
    # the all-reduce on device 0 only: (1 + 0) / 2 devices / 2 intervals
    assert r["collective_ms"] == pytest.approx(250.0)
    ops = dict(r["device_ops"])
    assert "%while.1" not in ops  # it holds other ops
    assert ops["%fusion.3"] == pytest.approx(1.5)
    assert ops["%copy.4"] == pytest.approx(1.0)
    # device 0's gaps: [0, 1], [9, 11], [13, 14], [15, 20]
    gaps = r["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([5.0, 2.0, 1.0, 1.0])
    assert gaps[0][0] == "interval"
    assert gaps[1][0] == "no host event"  # [9, 11] at 10: between spans


def test_combine_adds_single_interval_traces():
    host = {"names": ["interval"], "id": [0], "start": [0.0], "end": [10.0]}
    a = {"names": ["%fusion.1", "%fusion.2"], "id": [0, 1],
         "start": [1.0, 4.0], "end": [3.0, 9.0]}
    b = {"names": ["%fusion.2"], "id": [0], "start": [0.0], "end": [4.0]}
    host_b = dict(host, end=[5.0])
    ra = devtrace.reduce(as_trace({"devices": [a], "host": host}))
    rb = devtrace.reduce(as_trace({"devices": [b], "host": host_b}))
    r = devtrace.combine([ra, None, rb])
    assert r["window_s"] == 15.0
    assert r["busy_s"] == pytest.approx(11.0)
    assert r["intervals"] == 2
    assert r["device_ms"] == pytest.approx(5.5e3)
    assert dict(r["device_ops"]) == pytest.approx({"%fusion.2": 9.0, "%fusion.1": 2.0})
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx([1.0, 1.0, 1.0, 1.0])
    assert r["collective_ms"] is None
    assert devtrace.combine([None]) is None


def sweep_union(start, end, a, b):
    """Covered length of [a, b], by a plain walk over sorted intervals."""
    total, reach = 0.0, a
    for s, e in sorted(zip(start, end)):
        s, e = max(s, reach), min(e, b)
        if e > s:
            total += e - s
            reach = e
    return total


def test_recorded_trace():
    tr = as_trace(json.loads((HERE / "hall_excerpt.json").read_text()))
    r = devtrace.reduce(tr)
    h = tr["host"]
    is_span = np.asarray([n == devtrace.SPAN for n in h["names"]])[h["id"]]
    spans = list(zip(h["start"][is_span], h["end"][is_span]))
    w0, w1 = spans[0][0], spans[-1][1]
    busy = [sweep_union(d["start"], d["end"], w0, w1) for d in tr["devices"]]
    assert r["busy_s"] == pytest.approx(np.mean(busy), rel=1e-9)
    per = [
        np.mean([sweep_union(d["start"], d["end"], a, b) for a, b in spans])
        for d in tr["devices"]
    ]
    assert r["device_ms"] == pytest.approx(1e3 * np.mean(per), rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    coll = []
    for d in tr["devices"]:
        keep = [bool(devtrace.COLLECTIVE.search(d["names"][i])) for i in d["id"]]
        coll.append(sweep_union(d["start"][keep], d["end"][keep], w0, w1))
    if sum(coll) > 0:
        assert r["collective_ms"] == pytest.approx(
            1e3 * np.mean(coll) / len(spans), rel=1e-9
        )
    else:
        assert r["collective_ms"] is None
