"""nvPAX — Algorithm 3: the full three-phase power allocation policy.

``optimize()`` is the public entry point for one control step's problem.  It
is deterministic, always returns a feasible allocation (exact repair, section
"repair" of phases.py), and supports warm starting across control steps
(paper section 5.6 "additional speedups are possible via ... warm-starting
across control steps").  It runs the jitted three-phase driver
:func:`repro.core.batched.solve_three_phase` as a one-lane batch: the same
compiled program :func:`repro.core.batched.optimize_batched` serves K lanes
with, and the one :class:`repro.core.engine.AllocEngine` steps.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np

from repro.core import phases
from repro.core import solver as solver_mod
from repro.core.problem import AllocProblem
from repro.obs.stats import StepStats

__all__ = ["AllocResult", "NvpaxOptions", "optimize"]


@dataclass(frozen=True)
class NvpaxOptions:
    eps: float = 1e-5  # paper's regularization weight
    solver: solver_mod.SolverOptions = field(default_factory=solver_mod.SolverOptions)
    run_phase2: bool = True
    run_phase3: bool = True
    max_rounds: int = phases.MAX_ROUNDS
    x64: bool = True  # solve in float64 (jax.enable_x64 context)
    # exact fast paths on SLA-free problems (beyond-paper optimization):
    # Phase I's level QPs as a tree projection, and the max-min phases as
    # water-filling (equals the iterated-LP limit)
    use_waterfill: bool = True
    # Anytime / deadline-aware mode (the paper's stated future work,
    # section 6): every phase boundary is a valid, feasible allocation, so
    # the deadline is translated into a PDHG iteration budget via a
    # calibrated per-iteration cost; the refinement phases (II: active
    # surplus, III: idle surplus) are skipped or cut at saturation-round
    # granularity and the best-so-far allocation is returned with
    # stats["truncated"]=True.  Phase I always runs: it carries feasibility
    # and request satisfaction.
    deadline_s: float | None = None
    # Incremental re-solve (PR 7): certify the carried solution against the
    # new step before solving (see repro.core.solver.certify).  When enabled,
    # callers thread ``AllocResult.carry`` back in and get
    # stats["skipped"]/stats["certify_pass"] on every path.  ``certify_tol``
    # is the "unchanged" comparison tolerance in watts; ``certify_margin`` is
    # the slack margin below which a demand/cap move forces a full solve.
    incremental: bool = False
    certify_tol: float = 1e-9
    certify_margin: float = 1e-2


@dataclass
class AllocResult:
    allocation: np.ndarray  # [n] final feasible allocation (phase III output)
    phase1: np.ndarray
    phase2: np.ndarray
    warm_state: Any  # phases.WarmCarry for the next control step
    wall_time_s: float
    stats: dict[str, Any]
    # incremental-mode anchor for the next step's certify pass (None unless
    # options.incremental; see repro.core.solver.certify.IncrementalCarry)
    carry: Any = None


def optimize(
    ap: AllocProblem,
    options: NvpaxOptions = NvpaxOptions(),
    warm: phases.WarmCarry | None = None,
    carry: Any = None,
) -> AllocResult:
    """Run Algorithm 3 on one control step's problem.

    ``warm`` is the per-phase carry returned as ``AllocResult.warm_state``
    by the previous control step (see :class:`repro.core.phases.WarmCarry`);
    it is an optimization, not a correctness dependency — warm and cold
    steps agree to solver tolerance.

    ``carry`` (with ``options.incremental``) is the previous step's
    :class:`~repro.core.solver.certify.IncrementalCarry` anchor: the carried
    solution is certified against the new step first, and on success the
    solve is skipped entirely (``stats["skipped"]``) or restarted after
    Phase I (``stats["certify_pass"]``) — see ``repro.core.solver.certify``.
    """
    # batched imports NvpaxOptions from this module
    from repro.core import batched

    def lane(tree):
        return jax.tree_util.tree_map(lambda a: a[None], tree)

    def unlane(tree):
        return jax.tree_util.tree_map(lambda a: a[0], tree)

    out, budget, wall = batched._run_batched(
        [ap],
        options,
        lane(warm),
        carry=lane(carry) if options.incremental else None,
    )
    x1, x2, x3, warm_state, stats, new_carry, _ = out
    stats = jax.tree_util.tree_map(lambda a: np.asarray(a)[0], jax.device_get(stats))
    return AllocResult(
        allocation=np.asarray(x3[0]),
        phase1=np.asarray(x1[0]),
        phase2=np.asarray(x2[0]),
        warm_state=unlane(warm_state),
        wall_time_s=wall,
        stats=StepStats.from_jit(stats, scalar=True, iter_budget=budget),
        carry=unlane(new_carry) if options.incremental else None,
    )
