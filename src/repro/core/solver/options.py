"""Public datatypes of the :mod:`repro.core.solver` package.

:class:`SolverOptions` is hashable static metadata: one jitted solve program
per distinct value.  The knobs added by the solver-core overhaul (diagonal
preconditioning, adaptive restarts, the no-progress certificate) extend the
tuple *at the end* so existing keyword construction sites keep working.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax.numpy as jnp

__all__ = [
    "KKT_HIST_BUCKETS",
    "KKT_HIST_LO_EXP",
    "SolverOptions",
    "SolverState",
    "SolveStats",
]

# Shape of the in-loop KKT-score histogram accumulated by the solve loop
# (and re-used by the flight recorder's log-bucketed gauges): bucket ``b``
# holds scores in ``[10**(LO_EXP+b), 10**(LO_EXP+b+1))``, clipped at both
# ends.  Fixed module constants — not SolverOptions knobs — so the stats
# pytree shape is identical across option values.
KKT_HIST_BUCKETS = 16
KKT_HIST_LO_EXP = -12


class SolverOptions(NamedTuple):
    eps_abs: float = 1e-6
    eps_rel: float = 1e-6
    max_iters: int = 50_000
    check_every: int = 50  # KKT check cadence (iterations)
    # maximum chunks between restarts.  With ``adaptive_restarts`` this is
    # the *artificial* restart cadence (the KKT-progress triggers usually
    # fire first); without it, the fixed restart period of the old solver.
    restart_every: int = 8
    # step-size safety: tau_j * sigma_i * |K_ij| row/col sums <= theta^2
    theta: float = 0.9
    omega0: float = 0.0  # initial primal weight; <= 0 -> auto
    power_iters: int = 40  # only used when precondition=False
    # fused Pallas update kernels (repro.kernels.pdhg_update) for the
    # n-sized primal/dual blocks of the inner iteration; the tiny SLA block
    # and the scalar t stay jnp.  Parity with the pure-jnp path is asserted
    # in tests/test_kernels.py.
    # The kernels compile for Mosaic on a TPU backend and run the traced
    # interpreter elsewhere (repro.kernels.resolve_interpret).
    use_pallas: bool = False
    # -- solver-core overhaul knobs (PR 5) ---------------------------------
    # Diagonal (Pock-Chambolle) step sizes computed in closed form from the
    # tree/SLA incidence; False falls back to scalar steps from the global
    # operator-norm power iteration (the pre-overhaul behavior).
    precondition: bool = True
    # KKT-progress restart triggers (PDLP's sufficient/necessary decay
    # factors); False restarts on the fixed ``restart_every`` cadence only.
    adaptive_restarts: bool = True
    restart_beta_suff: float = 0.2
    restart_beta_nec: float = 0.8
    # consecutive no-improvement KKT checks before a stall forces a restart
    # (each restart re-estimates the primal weight, which is what un-sticks
    # degenerate LPs whose primal freezes while the duals tug-of-war)
    stall_checks: int = 2
    # no-progress / optimal-vertex certificate: exit when the primal iterate
    # has moved less than ``noprogress_tol`` (relative) for
    # ``noprogress_patience`` consecutive checks AND the t-polished iterate
    # is primal-feasible to tolerance.  0 disables the certificate.
    noprogress_tol: float = 1e-9
    noprogress_patience: int = 4
    # exact epigraph polish on exit: t <- clip(min_i(x_i - imp_lo_i)); the
    # max-min LP's scalar converges an order slower than x on degenerate
    # geometries, so the certificate exit recovers t* from the settled x.
    polish_t: bool = True
    # -- sharded-dispatch / Pallas-native knobs (PR 6) ---------------------
    # Route the tree prefix / SLA segment matvecs of the inner iteration
    # through the chunked Pallas kernels (repro.kernels.tree_matvec) instead
    # of the plain jnp prefix_sum/segment_sum in repro.core.treeops.
    use_pallas_tree: bool = False
    # Fuse the between-chunk restart/KKT bookkeeping (average accumulation,
    # no-progress move norms, restart-candidate travel distances) into
    # single-pass kernel epilogues (repro.kernels.pdhg_update chunk stats)
    # instead of separate jnp reductions.  Reduction *association* differs
    # from jnp (per-block partials), so iterate trajectories may diverge at
    # roundoff; allocations agree to solver tolerance.
    use_pallas_stats: bool = False
    # Per-dual-block primal weights (PDLP multi-block style): a second
    # omega for the SLA rows, re-estimated from SLA dual travel at each
    # restart, with tau_x recomputed from the omega-weighted per-block
    # column sums so the Pock-Chambolle bound still holds by construction.
    # Requires precondition=True (silently inert otherwise / without SLAs).
    blockwise_omega: bool = False


class SolverState(NamedTuple):
    """Warm-startable solver state in ORIGINAL units (primal + duals)."""

    x: jnp.ndarray  # [n]
    t: jnp.ndarray  # scalar
    y_tree: jnp.ndarray  # [m] duals (original metric)
    y_sla: jnp.ndarray  # [k]
    y_imp: jnp.ndarray  # [n]

    @classmethod
    def zeros(cls, n: int, m: int, k: int, dtype) -> "SolverState":
        z = functools.partial(jnp.zeros, dtype=dtype)
        return cls(z((n,)), z(()), z((m,)), z((k,)), z((n,)))


class SolveStats(NamedTuple):
    iterations: jnp.ndarray  # int32
    primal_res: jnp.ndarray
    dual_res: jnp.ndarray
    comp_res: jnp.ndarray
    # exited on a certificate (KKT or no-progress) rather than max_iters
    converged: jnp.ndarray  # bool
    omega: jnp.ndarray
    # KKT-certified to tolerance; ``converged & ~certified`` is the
    # no-progress/optimal-vertex certificate (see solver.termination)
    certified: jnp.ndarray  # bool
    restarts: jnp.ndarray  # int32
    # [KKT_HIST_BUCKETS] int32: log10-bucketed KKT scores observed at the
    # in-loop termination checks (flight-recorder substrate, PR 8)
    score_hist: jnp.ndarray
