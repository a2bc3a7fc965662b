"""Matrix-free primal-dual solver (PDHG / PDLP-lite) for nvPAX programs.

The paper solves Phase I with a sparse interior-point QP (Clarabel) and
Phases II/III with HiGHS — CPU-only machinery built around sparse
factorizations.  This package is the TPU-native replacement (DESIGN.md
section 2): a Chambolle-Pock primal-dual iteration whose only
non-elementwise work is the structured constraint matvec of
:mod:`repro.core.treeops` (prefix sums + gathers + segment sums), shared by every
consumer — the three-phase driver (:mod:`repro.core.batched`), the
persistent :class:`~repro.core.engine.AllocEngine`, and the fleet
orchestrator's stacked/loop dispatch.

Layout (the stable facade is this module's namespace):

* :mod:`~repro.core.solver.options` — :class:`SolverOptions` /
  :class:`SolverState` / :class:`SolveStats`;
* :mod:`~repro.core.solver.scaling` — curvature-aware metric scaling,
  analytic row equilibration, pinned-column fold-out, and the diagonal
  Pock-Chambolle step sizes computed from the tree/SLA incidence (no global
  operator-norm power iteration on the default path);
* :mod:`~repro.core.solver.restarts` — PDLP-style adaptive restarts:
  KKT-progress triggers (sufficient/necessary decay, stall), restart to the
  better of iterate/average, primal-weight re-estimation from travel
  distances;
* :mod:`~repro.core.solver.termination` — KKT residuals in the original
  metric (tolerances mean watts) plus the no-progress/optimal-vertex
  certificate with exact epigraph t-polish, which bounds the iteration cost
  of degenerate max-min rounds;
* :mod:`~repro.core.solver.loop` — the fixed-shape ``lax.while_loop``
  program tying it together; jits once per (n, m, k, options) and is
  vmap-safe.
"""

from repro.core.solver.certify import (
    CertifyDecision,
    IncrementalCarry,
    certify_step,
    make_carry,
    update_carry,
)
from repro.core.solver.loop import solve
from repro.core.solver.options import (
    KKT_HIST_BUCKETS,
    KKT_HIST_LO_EXP,
    SolveStats,
    SolverOptions,
    SolverState,
)
from repro.core.solver.scaling import (
    Scales,
    StepSizes,
    estimate_norm,
    make_scales,
    pc_step_sizes,
    uniform_step_sizes,
)
from repro.core.solver.termination import kkt_residuals, polish_t, primal_residual

__all__ = [
    "KKT_HIST_BUCKETS",
    "KKT_HIST_LO_EXP",
    "SolverOptions",
    "SolverState",
    "SolveStats",
    "solve",
    "kkt_residuals",
    "primal_residual",
    "polish_t",
    "IncrementalCarry",
    "CertifyDecision",
    "certify_step",
    "make_carry",
    "update_carry",
    "Scales",
    "StepSizes",
    "make_scales",
    "pc_step_sizes",
    "uniform_step_sizes",
    "estimate_norm",
]
