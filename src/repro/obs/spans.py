"""Host-side wall-clock spans for the control interval's Python stages.

The in-jit recorder (:mod:`repro.obs.recorder`) sees everything the compiled
step program does, but a control interval also spends wall time in host code:
telemetry decode, coordinator planning, dispatch bookkeeping, result fetch.
Spans cover that half — nestable, thread-local, near-free when disabled
(one attribute check per call site, no annotation opened).

Usage::

    from repro.obs import spans

    spans.enable()
    with spans.span("fleet.plan"):
        plan = coordinator.plan(...)
    ...
    print(spans.summary())   # {"fleet.plan": {"count": ..., "p95_ms": ...}}

Span names nest by the runtime stack: a ``span("solve")`` opened inside
``span("fleet.step")`` records as ``fleet.step/solve``, so the summary
shows where each parent's time actually went.

Profiler: an enabled :func:`span` also opens a
``jax.profiler.TraceAnnotation`` of its path, so inside a profiler session
(:func:`profile_trace`, or an outside ``jax.profiler.start_trace``) the host
stages land on the profiler's own clock beside the device operations; outside
a session the annotation records nothing.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Any, Callable, Iterator

import jax
import numpy as np

__all__ = [
    "enable",
    "disable",
    "enabled",
    "span",
    "traced",
    "drain",
    "reset",
    "summary",
    "profile_trace",
]

_lock = threading.Lock()
_records: list[tuple[str, float, float]] = []  # (path, t0, duration_s)
_local = threading.local()

_enabled = False
_OFF = contextlib.nullcontext()


def enable() -> None:
    """Turn span recording (and the spans' profiler annotations) on."""
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def _stack() -> list[str]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def span(name: str) -> contextlib.AbstractContextManager:
    """Record a named wall-clock span (no-op unless :func:`enable` ran)."""
    if not _enabled:
        return _OFF
    return _record(name)


@contextlib.contextmanager
def _record(name: str) -> Iterator[None]:
    stack = _stack()
    path = "/".join(stack + [name]) if stack else name
    stack.append(name)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(path):
            yield
    finally:
        dur = time.perf_counter() - t0
        stack.pop()
        with _lock:
            _records.append((path, t0, dur))


def traced(name: str) -> Callable:
    """Decorator form of :func:`span` for whole host-stage functions."""

    def deco(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def drain() -> list[dict[str, Any]]:
    """Pop and return every recorded span as ``{"span", "t0", "ms"}``."""
    with _lock:
        recs, _records[:] = _records[:], []
    return [{"span": p, "t0": t0, "ms": d * 1e3} for p, t0, d in recs]


def reset() -> None:
    with _lock:
        _records[:] = []


def summary(records: list[dict[str, Any]] | None = None) -> dict[str, dict]:
    """Per-path count/total/percentile summary (ms).  Pass the output of
    :func:`drain` to summarize without consuming the live buffer twice."""
    if records is None:
        with _lock:
            records = [{"span": p, "ms": d * 1e3} for p, _, d in _records]
    by_path: dict[str, list[float]] = {}
    for rec in records:
        by_path.setdefault(rec["span"], []).append(rec["ms"])
    out = {}
    for path, ms in sorted(by_path.items()):
        arr = np.asarray(ms)
        out[path] = {
            "count": len(ms),
            "total_ms": float(arr.sum()),
            "p50_ms": float(np.percentile(arr, 50)),
            "p95_ms": float(np.percentile(arr, 95)),
            "p99_ms": float(np.percentile(arr, 99)),
        }
    return out


@contextlib.contextmanager
def profile_trace(log_dir: str) -> Iterator[None]:
    """Opt-in Perfetto capture: wraps ``jax.profiler.start_trace`` and turns
    spans on, so host stages appear alongside device ops in the dumped trace
    (load it at ui.perfetto.dev)."""
    global _enabled
    was_enabled = _enabled
    enable()
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        _enabled = was_enabled
