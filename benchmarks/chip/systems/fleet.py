"""A campus of halls through ``FleetOrchestrator``: the tree cut below the
campus feed into one domain per hall, the domains' solves sharded over the
chips' ``("domains",)`` mesh, the coordinator's demand exchange one ``psum``
a step, warm-started from the previous interval."""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
from deploy import Answer, uniform_pdn
from repro.core.nvpax import NvpaxOptions
from repro.fleet import FleetOrchestrator, FleetStepResult


def build(cfg: dict):
    """The step callable for one control interval: telemetry in, Answer out."""
    # an orchestrator that returns no per-phase caps cannot be checked:
    # refuse before compiling anything
    if "phase1" not in {f.name for f in dataclasses.fields(FleetStepResult)}:
        raise RuntimeError("FleetStepResult carries no phase1/phase2 caps")
    f64 = cfg["precision"] == "float64"
    orch = FleetOrchestrator(
        uniform_pdn(cfg),
        level=cfg["level"],
        mode="sharded",
        options=NvpaxOptions(x64=f64),
        idle_threshold=cfg["idle_threshold"],
        dtype=jnp.float64 if f64 else jnp.float32,
    )

    def step(telemetry):
        res = orch.step(telemetry)
        return Answer(
            res.allocation, res.phase1, res.phase2, int(np.sum(res.stats["iterations"]))
        )

    return step
