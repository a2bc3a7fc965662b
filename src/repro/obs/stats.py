"""Typed per-step solver statistics: one record shape for every path.

Before PR 8 the ``skipped``/``certify_pass``/``phase_iterations`` stats
plumbing was duplicated by hand across four producers — host
:func:`repro.core.nvpax.optimize`, :func:`repro.core.batched.optimize_batched`,
:class:`repro.core.engine.AllocEngine`, and the fleet orchestrator's three
dispatch modes — each with slightly different key spellings
(``total_solves`` vs ``solves``, ``phase_iterations`` vs
``iterations_per_phase``).  :class:`StepStats` is the single constructor all
of them emit now.

It subclasses ``dict`` so every existing consumer keeps working unchanged
(`res.stats["total_solves"]`, ``stats.get("skipped", False)``, per-step
mutation like the orchestrator's ``stats["slice_lo"] = ...``); the canonical
*and* alias spellings are both present as keys, and canonical fields are
additionally readable as attributes (``stats.solves``).
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np

__all__ = ["StepStats"]

# canonical name -> legacy alias also stored as a key
_ALIASES = {
    "solves": "total_solves",
    "iterations": "total_iterations",
    "phase_iterations": "iterations_per_phase",
}


class StepStats(dict):
    """Per-step solver statistics (dict-compatible typed record).

    Canonical fields: ``solves``, ``iterations``, ``phase_iterations``
    (``[3]`` or ``[K, 3]``), ``converged``, ``skipped``, ``certify_pass``,
    and (when the producing path reports them) ``kkt_certified``,
    ``truncated``, ``kkt_res``, ``restarts``, ``kkt_hist``,
    ``waterfill_rounds`` and ``waterfill_levels`` (each ``[2]`` or
    ``[K, 2]``, Phases II and III, on the SLA-free path that runs no PDHG
    iteration: the sequential search steps of the max-min fill, which its
    device time scales with, and the tree levels whose search ran, 0 where
    no node binds), and ``project_steps_p1`` and ``project_levels_p1`` (a
    scalar or ``[K]``: the same two counts for Phase I's tree projection on
    that path).  Values are Python scalars on the engine path and numpy
    arrays on batched/fleet paths — the record is shape-agnostic on purpose.

    Extras by path: the fleet's sharded dispatch adds
    ``coordinator_rounds`` (an ``int``: the rounds of the coordinator
    plan's two ``waterfill_jax`` grant passes, demand then headroom,
    computed replicated on every shard); the fleet adds ``slice_lo`` and
    ``slice_hi`` with cross-cut tenants; the engine adds ``iter_budget``.
    :class:`repro.fleet.FleetOrchestrator` ``history`` rows carry
    ``coordinator_rounds`` where the stats have it, and every mode's rows
    carry ``iterations_max`` and ``iterations_min``, the slowest and the
    fastest domain's iterations (the lockstep's straggler).
    """

    @classmethod
    def build(
        cls,
        *,
        solves: Any,
        iterations: Any,
        phase_iterations: Any,
        converged: Any,
        skipped: Any,
        certify_pass: Any,
        kkt_certified: Any = None,
        truncated: Any = None,
        kkt_res: Any = None,
        restarts: Any = None,
        kkt_hist: Any = None,
        waterfill_rounds: Any = None,
        waterfill_levels: Any = None,
        project_steps_p1: Any = None,
        project_levels_p1: Any = None,
        **extras: Any,
    ) -> "StepStats":
        out = cls()
        fields = {
            "solves": solves,
            "iterations": iterations,
            "phase_iterations": phase_iterations,
            "converged": converged,
            "skipped": skipped,
            "certify_pass": certify_pass,
            "kkt_certified": kkt_certified,
            "truncated": truncated,
            "kkt_res": kkt_res,
            "restarts": restarts,
            "kkt_hist": kkt_hist,
            "waterfill_rounds": waterfill_rounds,
            "waterfill_levels": waterfill_levels,
            "project_steps_p1": project_steps_p1,
            "project_levels_p1": project_levels_p1,
        }
        for name, value in fields.items():
            if value is None:
                continue
            out[name] = value
            alias = _ALIASES.get(name)
            if alias is not None:
                out[alias] = value
        out.update(extras)
        return out

    @classmethod
    def from_jit(
        cls, stats: dict, *, scalar: bool = False, **extras: Any
    ) -> "StepStats":
        """Convert the traced stats dict of
        :func:`repro.core.batched.solve_three_phase` (keys ``solves``,
        ``iterations``, ``iterations_p1..3``, ``waterfill_rounds_p2..3``,
        ``waterfill_levels_p2..3``, ``project_steps_p1``,
        ``project_levels_p1``, flags) to host values, fetched from the
        device in one transfer.

        ``scalar=True`` is the one-scenario path (the engine's ``step`` and
        :func:`repro.core.nvpax.optimize`, which strips the lane axis
        first): leaves become Python ``int``/``bool``/``float`` scalars.
        """
        stats = jax.device_get(dict(stats))
        pi = np.stack(
            [np.asarray(stats[f"iterations_p{i}"]) for i in (1, 2, 3)], axis=-1
        )
        wr, wl = (
            np.stack([np.asarray(stats[f"{key}_p{i}"]) for i in (2, 3)], axis=-1)
            for key in ("waterfill_rounds", "waterfill_levels")
        )
        if scalar:
            return cls.build(
                solves=int(stats["solves"]),
                iterations=int(stats["iterations"]),
                phase_iterations=[int(v) for v in pi],
                converged=bool(stats["converged"]),
                skipped=bool(stats["skipped"]),
                certify_pass=bool(stats["certify_pass"]),
                kkt_certified=bool(stats["kkt_certified"]),
                truncated=bool(stats["truncated"]),
                kkt_res=float(stats["kkt_res"]),
                restarts=int(stats["restarts"]),
                kkt_hist=np.asarray(stats["kkt_hist"]),
                waterfill_rounds=[int(v) for v in wr],
                waterfill_levels=[int(v) for v in wl],
                project_steps_p1=int(stats["project_steps_p1"]),
                project_levels_p1=int(stats["project_levels_p1"]),
                **extras,
            )
        return cls.build(
            solves=np.asarray(stats["solves"]),
            iterations=np.asarray(stats["iterations"]),
            phase_iterations=pi,
            converged=np.asarray(stats["converged"]),
            skipped=np.asarray(stats["skipped"]),
            certify_pass=np.asarray(stats["certify_pass"]),
            kkt_certified=np.asarray(stats["kkt_certified"]),
            truncated=np.asarray(stats["truncated"]),
            kkt_res=np.asarray(stats["kkt_res"]),
            restarts=np.asarray(stats["restarts"]),
            kkt_hist=np.asarray(stats["kkt_hist"]),
            waterfill_rounds=wr,
            waterfill_levels=wl,
            project_steps_p1=np.asarray(stats["project_steps_p1"]),
            project_levels_p1=np.asarray(stats["project_levels_p1"]),
            **extras,
        )

    def __getattr__(self, name: str):
        try:
            return self[name]
        except KeyError:
            alias = _ALIASES.get(name)
            if alias is not None and alias in self:
                return self[alias]
            raise AttributeError(name) from None
