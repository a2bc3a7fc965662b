"""Device time by named scope, and host time by span, of traced control
intervals.

The step program names its parts with ``jax.named_scope`` (``phase1`` to
``phase3``, ``pdhg``, ``waterfill``, ``repair``, ``certify``).  A scope
reaches the compiled program only as the ``op_name`` metadata of its HLO
instructions, and a TPU profile does not carry that metadata on its
operation events (an ``XLA Ops`` event holds the instruction's text without
it, and a start and a duration).  So ``op_names`` reads the metadata from
the compiled program's text (``Compiled.as_text()``), keyed by instruction
name, and ``scope_ms`` joins it with the operations of a trace loaded by
``devtrace.load``.

- ``scope_ms``: per interval span, the union of the device operations that
  hold no other operation and whose ``op_name`` has the scope as one of its
  ``/``-separated parts, averaged over devices and intervals.  Leaves of
  one device do not overlap, so the scopes of disjoint parts of the program
  add up to at most ``devtrace``'s ``device_ms``.
- ``program_spans``: the trace with its host events cut to the interval
  spans and the program's own spans.  The profiler's Python tracer puts a
  frame event around every Python call, so in the full trace
  ``devtrace``'s idle-gap label, the innermost host event, is a frame such
  as ``_array.py:631 _value``; in the cut trace it is the innermost span of
  :mod:`repro.obs.spans` (``engine.step/engine.fetch``).
- ``host_ms``: per ``engine.step`` span, its time less that of its
  ``engine.wait`` child: the host work of the step.
"""

from __future__ import annotations

import re

import numpy as np

import devtrace

_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{op_name="([^"]*)"')


def op_names(hlo_text: str) -> dict[str, str]:
    """HLO instruction name (``fusion.12``) -> its ``op_name`` metadata."""
    out = {}
    for line in hlo_text.splitlines():
        if (m := _INSTR.match(line)) is not None:
            out[m.group(1)] = m.group(2)
    return out


def in_scope(op_name: str, scope: str) -> bool:
    return scope in op_name.split("/")


def scope_ms(tr: dict, names: dict[str, str], scope: str) -> float | None:
    """Device milliseconds per interval in leaf operations under ``scope``;
    None when the trace holds no interval span or no device operation."""
    host = tr["host"]
    is_span = np.asarray([n == devtrace.SPAN for n in host["names"]], bool)[host["id"]]
    spans = list(zip(host["start"][is_span], host["end"][is_span]))
    devices = [d for d in tr["devices"] if d["id"].size]
    if not spans or not devices:
        return None
    per_device = []
    for d in devices:
        scoped = [in_scope(names.get(n.lstrip("%"), ""), scope) for n in d["names"]]
        keep = np.asarray(scoped, bool)[d["id"]] & devtrace.leaves(d["start"], d["end"])
        ms, me = devtrace.merge(d["start"][keep], d["end"][keep])
        per_device.append(np.mean([devtrace.covered(ms, me, a, b) for a, b in spans]))
    return 1e3 * float(np.mean(per_device))


def matched_share(tr: dict, names: dict[str, str]) -> float:
    """Share of leaf-operation time whose instruction ``names`` knows: near 1
    when the text is that of the traced program."""
    hit = total = 0.0
    for d in tr["devices"]:
        if not d["id"].size:
            continue
        leaf = devtrace.leaves(d["start"], d["end"])
        known = np.asarray([n.lstrip("%") in names for n in d["names"]], bool)[d["id"]]
        dur = d["end"] - d["start"]
        hit += float(dur[leaf & known].sum())
        total += float(dur[leaf].sum())
    return hit / total if total else 0.0


def program_spans(tr: dict, prefix: str = "engine.") -> dict:
    """``tr`` with only the interval spans and the spans named ``prefix...``
    among its host events."""
    host = tr["host"]
    named = [n == devtrace.SPAN or n.startswith(prefix) for n in host["names"]]
    keep = np.asarray(named, bool)[host["id"]]
    cut = {k: host[k][keep] for k in ("id", "start", "end")}
    return {**tr, "host": {"names": host["names"], **cut}}


def host_ms(records: list[dict]) -> list[float]:
    """Host milliseconds of each ``engine.step`` in ``records`` (as
    ``spans.drain()`` returns them, children before their parent)."""
    out, wait = [], 0.0
    for r in records:
        if r["span"] == "engine.step/engine.wait":
            wait += r["ms"]
        elif r["span"] == "engine.step":
            out.append(r["ms"] - wait)
            wait = 0.0
    return out
