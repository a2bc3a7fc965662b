"""Certify-first incremental stepping (PR 7).

Production power telemetry is strongly autocorrelated between control
intervals (PAPERS.md: Prediction-Based Power Oversubscription builds its
whole oversubscription story on that; CloudPowerCap re-budgets only on
demand/capacity *events*).  This module exploits it: before launching the
PDHG loop, one fused feasibility/optimality pass checks whether the
*carried* solution still solves the new step, and if so the solve is
skipped in O(matvec).

The certificate has two tiers, both fully traced (fixed shapes, no
recompilation across skip/solve transitions):

* **full skip** — the carried final allocation is returned unchanged,
  with the anchor's Phase I and Phase II allocations as its phases.
  Sound when the binding-set fingerprint is unchanged — same active mask,
  box edges, tree caps and SLA rows within ``certify_tol`` watts — and
  every shaped demand is held within ``certify_tol`` of the anchor value
  it was solved against.  The bar is deliberately exact-match: the
  max-min refinement raises allocations by a *uniform increment over the
  Phase I point* (``lp_step``'s ``a_i - base_i >= t`` rows), so even a
  device holding large surplus has a final allocation that tracks its
  request ~1:1 and a "demand moved but stays under slack" relaxation
  would be unsound.  The carried point is additionally passed through the
  exact repair projection and a fused primal-feasibility residual (one
  tree matvec + reductions, routed through the ``use_pallas_tree`` kernels
  when enabled) before it is accepted.
* **Phase I skip** — demands are unchanged but tree caps moved (the fleet
  grant-drift case).  If every changed cap keeps at least
  ``certify_margin`` watts of Phase I slack under both its old and new
  value, the carried Phase I point is still optimal and only the cheap
  Phase II/III refinement re-runs against the new caps.

Both tiers are conservative by construction; the 200-step mixed-trace
parity regression in ``tests/test_incremental.py`` asserts ≤1e-6 W
against always-full-solve.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import phases, treeops
from repro.core.problem import AllocProblem
from repro.core.solver.options import SolverOptions

__all__ = ["IncrementalCarry", "CertifyDecision", "make_carry", "certify_step", "update_carry"]


class IncrementalCarry(NamedTuple):
    """Accepted-step snapshot the certificate is checked against.

    ``r``/``x1``/``lo``/``hi`` are the *anchor* values actually solved
    against — held-demand drift accumulates against the anchor, so a chain
    of skips cannot creep away from the certified point by more than
    ``certify_tol`` in total.
    """

    x1: jnp.ndarray  # [n] Phase I allocation of the anchor solve
    x2: jnp.ndarray  # [n] Phase II allocation of the anchor solve
    x: jnp.ndarray  # [n] final feasible allocation
    r: jnp.ndarray  # [n] shaped requests the anchor was solved against
    active: jnp.ndarray  # [n] bool activity mask
    lo: jnp.ndarray  # [n] box lower bounds
    hi: jnp.ndarray  # [n] box upper bounds
    cap: jnp.ndarray  # [m] tree node caps
    sla_lo: jnp.ndarray  # [k] tenant minimums
    sla_hi: jnp.ndarray  # [k] tenant caps


class CertifyDecision(NamedTuple):
    """Traced outcome of one certify pass (all leaves fixed-shape)."""

    skip: jnp.ndarray  # bool: carried allocation still optimal — skip all
    skip_p1: jnp.ndarray  # bool: carried Phase I reusable — re-run II/III only
    x_snap: jnp.ndarray  # [n] carried allocation after the repair projection
    feas_res: jnp.ndarray  # max primal-feasibility violation of x_snap (watts)


def make_carry(
    ap: AllocProblem, x1: jnp.ndarray, x2: jnp.ndarray, x3: jnp.ndarray
) -> IncrementalCarry:
    """Snapshot a freshly solved step as the next certify anchor."""
    return IncrementalCarry(
        x1=x1,
        x2=x2,
        x=x3,
        r=ap.r,
        active=ap.active,
        lo=ap.l,
        hi=ap.u,
        cap=ap.tree.cap,
        sla_lo=ap.sla.lo,
        sla_hi=ap.sla.hi,
    )


def _matvecs(x, tree, sla, opts: SolverOptions | None):
    """Tree + SLA row sums, routed through the chunked Pallas kernels on the
    ``use_pallas_tree`` path (same routing as the solver loop)."""
    if opts is not None and opts.use_pallas_tree:
        from repro.kernels import tree_matvec as tk

        kx = tk.tree_matvec(x, tree.start, tree.end)
        sx = (
            tk.sla_matvec(x, sla.dev, sla.ten, sla.k)
            if sla.k
            else treeops.sla_matvec(x, sla)
        )
    else:
        kx = treeops.tree_matvec(x, tree)
        sx = treeops.sla_matvec(x, sla)
    return kx, sx


@jax.named_scope("certify")
def certify_step(
    ap: AllocProblem,
    carry: IncrementalCarry,
    n_depths: int,
    *,
    tol: float,
    margin: float,
    opts: SolverOptions | None = None,
) -> CertifyDecision:
    """One fused certificate pass of the carried solution against ``ap``.

    Trace-safe and vmappable; ``n_depths``/``tol``/``margin`` are static.
    ``ap.r`` must already be shaped (clipped to the box, floored for idle
    devices) — both the engine and the fleet paths certify post-shaping.
    """
    dtype = ap.l.dtype
    tol_ = jnp.asarray(tol, dtype)
    margin_ = jnp.asarray(margin, dtype)

    def close(a, b):
        # exact equality first: inf == inf must count as unchanged
        return (a == b) | (jnp.abs(a - b) <= tol_)

    act_same = jnp.all(ap.active == carry.active)
    box_same = jnp.all(close(ap.l, carry.lo)) & jnp.all(close(ap.u, carry.hi))
    sla_same = jnp.all(close(ap.sla.lo, carry.sla_lo)) & jnp.all(
        close(ap.sla.hi, carry.sla_hi)
    )
    cap_close = close(ap.tree.cap, carry.cap)
    base_same = act_same & box_same & sla_same

    # demand fingerprint: every shaped request must match its anchor.  The
    # max-min refinement distributes surplus as a uniform increment over the
    # Phase I point, so any demand move shifts the optimum ~1:1 — there is
    # no sound "surplus-held" relaxation for the full-skip tier.
    all_held = jnp.all(jnp.abs(ap.r - carry.r) <= tol_)

    # snap: exact repair projection of the carried point against the new
    # problem, then a fused primal-feasibility residual (one tree matvec)
    x_snap = phases.repair(carry.x, ap, n_depths)
    snap_ok = jnp.max(jnp.abs(x_snap - carry.x)) <= margin_
    kx, sx = _matvecs(x_snap, ap.tree, ap.sla, opts)
    zero = jnp.zeros((), dtype)
    feas_res = jnp.maximum(
        jnp.max(jnp.maximum(kx - ap.tree.cap, zero)),
        jnp.maximum(
            jnp.max(jnp.maximum(x_snap - ap.u, zero)),
            jnp.max(jnp.maximum(ap.l - x_snap, zero)),
        ),
    )
    if ap.sla.k:
        feas_res = jnp.maximum(
            feas_res,
            jnp.maximum(
                jnp.max(jnp.maximum(ap.sla.lo - sx, zero)),
                jnp.max(jnp.maximum(sx - ap.sla.hi, zero)),
            ),
        )
    feas_ok = feas_res <= jnp.asarray(1e-7, dtype)

    skip = base_same & jnp.all(cap_close) & all_held & snap_ok & feas_ok

    # Phase I skip tier: frozen demands, caps moved but with Phase I slack
    # >= margin under both old and new value (fleet grant drift)
    p1_load, _ = _matvecs(carry.x1, ap.tree, ap.sla, opts)
    p1_slack_ok = p1_load <= jnp.minimum(ap.tree.cap, carry.cap) - margin_
    skip_p1 = (
        base_same & all_held & jnp.all(cap_close | p1_slack_ok) & ~skip
    )
    return CertifyDecision(skip=skip, skip_p1=skip_p1, x_snap=x_snap, feas_res=feas_res)


def update_carry(
    carry: IncrementalCarry | None,
    ap: AllocProblem,
    x1: jnp.ndarray,
    x2: jnp.ndarray,
    x3: jnp.ndarray,
    skipped: jnp.ndarray,
    p1_reused: jnp.ndarray,
) -> IncrementalCarry:
    """Next-step anchor: frozen on a full skip, Phase-I-anchored on a Phase I
    skip (new caps + new Phase II and final allocations), fresh after a full
    solve."""
    fresh = make_carry(ap, x1, x2, x3)
    if carry is None:
        return fresh
    keep_p1 = skipped | p1_reused

    def sel(pred, a, b):
        return jax.tree_util.tree_map(lambda u, v: jnp.where(pred, u, v), a, b)

    return IncrementalCarry(
        x1=sel(keep_p1, carry.x1, fresh.x1),
        x2=sel(skipped, carry.x2, fresh.x2),
        x=sel(skipped, carry.x, fresh.x),
        r=sel(keep_p1, carry.r, fresh.r),
        active=fresh.active,
        lo=sel(keep_p1, carry.lo, fresh.lo),
        hi=sel(keep_p1, carry.hi, fresh.hi),
        cap=sel(skipped, carry.cap, fresh.cap),
        sla_lo=sel(skipped, carry.sla_lo, fresh.sla_lo),
        sla_hi=sel(skipped, carry.sla_hi, fresh.sla_hi),
    )
