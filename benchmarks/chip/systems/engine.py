"""One hall through ``AllocEngine.step``: the compiled three-phase step on
one chip, warm-started from the previous interval."""

from __future__ import annotations

import jax.numpy as jnp
from deploy import Answer, uniform_pdn
from repro.core.engine import AllocEngine
from repro.core.nvpax import NvpaxOptions


def build(cfg: dict):
    """The step callable for one control interval: telemetry in, Answer out."""
    f64 = cfg["precision"] == "float64"
    eng = AllocEngine(
        uniform_pdn(cfg),
        options=NvpaxOptions(x64=f64),
        idle_threshold=cfg["idle_threshold"],
        dtype=jnp.float64 if f64 else jnp.float32,
    )

    def step(telemetry):
        res = eng.step(telemetry)
        return Answer(
            res.allocation, res.phase1, res.phase2, sum(res.stats["phase_iterations"])
        )

    return step
