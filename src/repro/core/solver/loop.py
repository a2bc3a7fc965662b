"""The PDHG solve loop: fixed-shape ``lax.while_loop`` over scan chunks.

Composition of the package: :mod:`~repro.core.solver.scaling` supplies the
metric change and the diagonal (Pock-Chambolle) step sizes,
:mod:`~repro.core.solver.restarts` the adaptive restart policy and primal
weight updates, :mod:`~repro.core.solver.termination` the KKT residuals and
the no-progress/optimal-vertex certificate.  Everything jits once per
``(n, m, k)`` problem shape + :class:`SolverOptions` value and is reused
across priority levels, saturation rounds and control steps (warm-started).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.problem import StepProblem
from repro.core.solver import restarts as restarts_mod
from repro.core.solver import scaling, termination
from repro.core.solver.options import (
    KKT_HIST_BUCKETS,
    KKT_HIST_LO_EXP,
    SolveStats,
    SolverOptions,
    SolverState,
)
from repro.core.treeops import (
    SlaTopo,
    TreeTopo,
    sla_matvec,
    sla_rmatvec,
    tree_matvec,
    tree_rmatvec,
)

__all__ = ["solve"]


def _dual_prox(z, sigma, lo, hi):
    """prox of sigma * g* for g = indicator[lo, hi]:  z - sigma*clip(z/sigma).
    ``sigma`` may be a scalar or a per-row vector (preconditioned form)."""
    return z - sigma * jnp.clip(z / sigma, lo, hi)


@functools.partial(jax.jit, static_argnames=("opts",))
@jax.named_scope("pdhg")
def solve(
    prob: StepProblem,
    tree: TreeTopo,
    sla: SlaTopo,
    init: SolverState,
    opts: SolverOptions = SolverOptions(),
) -> tuple[SolverState, SolveStats]:
    """Solve one unified QP/LP.  Returns (state, stats); ``state.x`` is the
    allocation *before* the exact feasibility repair done by the caller."""
    n = prob.n
    dtype = prob.lo.dtype
    m, k = tree.m, sla.k
    inf = jnp.asarray(jnp.inf, dtype)

    sc = scaling.make_scales(prob, tree, sla)
    if opts.precondition:
        steps = scaling.pc_step_sizes(prob, tree, sla, sc, opts.theta)
    else:
        steps = scaling.uniform_step_sizes(
            tree, sla, sc, n, opts.theta, opts.power_iters, dtype
        )

    # problem data in the scaled metric
    w_s = prob.w * sc.s * sc.s  # 1 for curved vars, 0 for linear
    target_s = prob.target / sc.s
    c_s = prob.c * sc.s
    ct_s = prob.c_t * sc.s_t
    lo_s = prob.lo / sc.s
    hi_s = prob.hi / sc.s
    tlo_s = prob.t_lo / sc.s_t
    thi_s = prob.t_hi / sc.s_t

    # fold pinned-variable contributions into the row bounds (their columns
    # are zeroed in the scaled operator; see scaling.make_scales)
    pin_x = jnp.where(sc.mov > 0, 0.0, prob.lo)
    pin_t = jnp.where(sc.t_mov > 0, 0.0, prob.t_lo)
    kpin_tree = tree_matvec(pin_x, tree)
    kpin_sla = sla_matvec(pin_x, sla)
    kpin_imp = pin_x - pin_t

    # scaled, pin-folded row bounds
    tree_hi_s = sc.d_tree * (prob.tree_hi - kpin_tree)
    sla_lo_s = sc.d_sla * (prob.sla_lo - kpin_sla)
    sla_hi_s = sc.d_sla * (prob.sla_hi - kpin_sla)
    imp_lo_s = jnp.where(
        jnp.isfinite(prob.imp_lo), sc.d_imp * (prob.imp_lo - kpin_imp), -inf
    )
    neg_inf_tree = jnp.full((m,), -inf, dtype)
    pos_inf_imp = jnp.full((n,), inf, dtype)

    if opts.use_pallas or opts.use_pallas_stats:
        from repro.kernels.pdhg_update import ops as _pk

    # per-dual-block primal weights (PDLP multi-block style): the SLA rows
    # get their own omega, and tau_x is recomputed per iteration from the
    # omega-weighted per-block column sums (the pc_step_sizes column sum,
    # split by row block) so the Pock-Chambolle bound holds for any pair of
    # weights.  Needs the diagonal (preconditioned) steps and an SLA block.
    use_blockwise = bool(opts.blockwise_omega and opts.precondition and k > 0)
    if use_blockwise:
        sm_bw = sc.s * sc.mov
        act_bw = jnp.isfinite(prob.imp_lo).astype(dtype)
        col_sla_bw = sm_bw * sla_rmatvec(sc.d_sla, sla, n)
        col_rest_bw = sm_bw * (tree_rmatvec(sc.d_tree, tree, n) + sc.d_imp * act_bw)
        tiny_bw = jnp.asarray(1e-12, dtype)
        theta_bw = jnp.asarray(opts.theta, dtype)

    def pdhg_iter(carry, _):
        x, t, y_tree, y_sla, y_imp, omega, om_sla = carry
        if use_blockwise:
            tau_x = theta_bw / jnp.maximum(
                col_rest_bw / omega + col_sla_bw / om_sla, tiny_bw
            )
            sig_sla = steps.sig_sla / om_sla
        else:
            tau_x = omega * steps.tau_x
            sig_sla = steps.sig_sla / omega
        tau_t = omega * steps.tau_t
        sig_tree = steps.sig_tree / omega
        sig_imp = steps.sig_imp / omega
        gx, gt = scaling.scaled_rmatvec(
            y_tree,
            y_sla,
            y_imp,
            tree,
            sla,
            sc,
            n,
            use_kernels=opts.use_pallas_tree,
        )
        if opts.use_pallas:
            # fused primal prox + extrapolation, one HBM round-trip
            x1, xe = _pk.primal_update(x, gx, c_s, w_s, target_s, lo_s, hi_s, tau_x)
        else:
            # primal prox (diagonal quadratic + box)
            x1 = jnp.clip(
                (x - tau_x * (gx + c_s) + tau_x * w_s * target_s)
                / (1.0 + tau_x * w_s),
                lo_s,
                hi_s,
            )
            xe = 2.0 * x1 - x
        t1 = jnp.clip(t - tau_t * (gt + ct_s), tlo_s, thi_s)
        # dual with extrapolation
        te = 2.0 * t1 - t
        a_tree, a_sla, a_imp = scaling.scaled_matvec(
            xe,
            te,
            tree,
            sla,
            sc,
            use_kernels=opts.use_pallas_tree,
        )
        if opts.use_pallas:
            y_tree1 = _pk.dual_prox(y_tree, a_tree, sig_tree, neg_inf_tree, tree_hi_s)
            y_imp1 = _pk.dual_prox(y_imp, a_imp, sig_imp, imp_lo_s, pos_inf_imp)
        else:
            y_tree1 = _dual_prox(
                y_tree + sig_tree * a_tree, sig_tree, neg_inf_tree, tree_hi_s
            )
            y_imp1 = _dual_prox(y_imp + sig_imp * a_imp, sig_imp, imp_lo_s, pos_inf_imp)
        y_sla1 = (
            _dual_prox(y_sla + sig_sla * a_sla, sig_sla, sla_lo_s, sla_hi_s)
            if k
            else y_sla
        )
        return (x1, t1, y_tree1, y_sla1, y_imp1, omega, om_sla), None

    def run_chunk(state7):
        """opts.check_every PDHG iterations."""
        out, _ = lax.scan(pdhg_iter, state7, None, length=opts.check_every)
        return out

    def unscale(x, t, yt, ys, yi):
        # original metric: x = S x~ (pinned vars pinned by their box),
        # y_orig = D2 y~
        return SolverState(
            jnp.where(sc.mov > 0, sc.s * x, prob.lo),
            jnp.where(sc.t_mov > 0, sc.s_t * t, prob.t_lo),
            sc.d_tree * yt,
            sc.d_sla * ys,
            sc.d_imp * yi,
        )

    eps = jnp.asarray(opts.eps_abs, dtype)
    eps_rel = jnp.asarray(opts.eps_rel, dtype)
    eps_tot = eps + eps_rel

    n_chunks = opts.max_iters // opts.check_every
    use_cert = opts.noprogress_tol > 0 and opts.noprogress_patience > 0

    class Carry(NamedTuple):
        x: jnp.ndarray
        t: jnp.ndarray
        y_tree: jnp.ndarray
        y_sla: jnp.ndarray
        y_imp: jnp.ndarray
        omega: jnp.ndarray
        omega_sla: jnp.ndarray  # SLA-block primal weight (blockwise_omega)
        # averaging since last restart
        ax: jnp.ndarray
        at: jnp.ndarray
        ayt: jnp.ndarray
        ays: jnp.ndarray
        ayi: jnp.ndarray
        acount: jnp.ndarray
        # restart anchors (for primal-weight travel ratio)
        rx: jnp.ndarray
        ry_tree: jnp.ndarray
        ry_sla: jnp.ndarray
        ry_imp: jnp.ndarray
        # previous check's iterate (no-progress detection)
        px: jnp.ndarray
        pt: jnp.ndarray
        chunk: jnp.ndarray
        pres: jnp.ndarray
        dres: jnp.ndarray
        cres: jnp.ndarray
        score_prev: jnp.ndarray  # candidate score at the previous check
        score_restart: jnp.ndarray  # score right after the last restart
        chunks_since: jnp.ndarray  # checks since the last restart
        stall: jnp.ndarray  # consecutive no-improvement checks
        frozen: jnp.ndarray  # consecutive motionless checks
        restarts: jnp.ndarray
        done: jnp.ndarray
        certified: jnp.ndarray
        # [KKT_HIST_BUCKETS] int32: log10 buckets of the candidate KKT
        # score at each check (flight-recorder histogram substrate)
        score_hist: jnp.ndarray

    # In the scaled metric curvature is 1 and variable travel is O(1), so
    # omega = 1 is the natural start for both QP and LP; adaptive
    # rebalancing refines it.
    init_omega = (
        jnp.asarray(opts.omega0, dtype) if opts.omega0 > 0 else jnp.asarray(1.0, dtype)
    )
    # scale the warm-start state into the solve metric
    x0 = init.x / sc.s
    t0 = init.t / sc.s_t
    yt0 = init.y_tree / jnp.maximum(sc.d_tree, 1e-30)
    ys0 = init.y_sla / jnp.maximum(sc.d_sla, 1e-30) if k else init.y_sla
    yi0 = init.y_imp / jnp.maximum(sc.d_imp, 1e-30)
    c0 = Carry(
        x=x0,
        t=t0,
        y_tree=yt0,
        y_sla=ys0,
        y_imp=yi0,
        omega=init_omega,
        omega_sla=init_omega,
        ax=jnp.zeros_like(x0),
        at=jnp.zeros_like(t0),
        ayt=jnp.zeros_like(yt0),
        ays=jnp.zeros_like(ys0),
        ayi=jnp.zeros_like(yi0),
        acount=jnp.zeros((), dtype),
        rx=x0,
        ry_tree=yt0,
        ry_sla=ys0,
        ry_imp=yi0,
        px=x0,
        pt=t0,
        chunk=jnp.zeros((), jnp.int32),
        pres=jnp.asarray(jnp.inf, dtype),
        dres=jnp.asarray(jnp.inf, dtype),
        cres=jnp.asarray(jnp.inf, dtype),
        score_prev=jnp.asarray(jnp.inf, dtype),
        score_restart=jnp.asarray(jnp.inf, dtype),
        chunks_since=jnp.zeros((), jnp.int32),
        stall=jnp.zeros((), jnp.int32),
        frozen=jnp.zeros((), jnp.int32),
        restarts=jnp.zeros((), jnp.int32),
        done=jnp.asarray(False),
        certified=jnp.asarray(False),
        score_hist=jnp.zeros((KKT_HIST_BUCKETS,), jnp.int32),
    )

    def cond(c: Carry):
        return (~c.done) & (c.chunk < n_chunks)

    def body(c: Carry):
        x, t, yt, ys, yi, om, om_sla = run_chunk(
            (c.x, c.t, c.y_tree, c.y_sla, c.y_imp, c.omega, c.omega_sla)
        )
        cnt = c.acount + 1.0
        if opts.use_pallas_stats:
            # fused chunk-boundary bookkeeping: average accumulation + move
            # norms + restart-candidate travel, one streaming pass per block
            ax, move_num, move_den, dx2_cur, dx2_avg = _pk.primal_chunk_stats(
                x, c.px, c.rx, c.ax, cnt
            )
            ayt, dyt2_cur, dyt2_avg, dyt2_zero = _pk.dual_chunk_stats(
                yt, c.ry_tree, c.ayt, cnt
            )
            ayi, dyi2_cur, dyi2_avg, dyi2_zero = _pk.dual_chunk_stats(
                yi, c.ry_imp, c.ayi, cnt
            )
            at_ = c.at + t
            ays = c.ays + ys
        else:
            ax, at_ = c.ax + x, c.at + t
            ayt, ays, ayi = c.ayt + yt, c.ays + ys, c.ayi + yi

        # KKT of three restart candidates: the current iterate, the running
        # average, and the current primal with ZERO duals.  The zero-dual
        # candidate is the poisoned-warm-start escape hatch: when a topology
        # re-pin (supply derate, budget grant) invalidates carried duals,
        # the complementarity residual of the carried state is catastrophic
        # while dropping the duals costs only a cold dual transient — the
        # candidate wins the comparison exactly when that trade is right.
        p, d, cm = termination.kkt_residuals(unscale(x, t, yt, ys, yi), prob, tree, sla)
        score = jnp.maximum(jnp.maximum(p, d), cm)
        xa, ta = ax / cnt, at_ / cnt
        yta, ysa, yia = ayt / cnt, ays / cnt, ayi / cnt
        pa, da, ca = termination.kkt_residuals(
            unscale(xa, ta, yta, ysa, yia), prob, tree, sla
        )
        score_a = jnp.maximum(jnp.maximum(pa, da), ca)
        pz, dz, cz = termination.kkt_residuals(
            unscale(x, t, jnp.zeros_like(yt), jnp.zeros_like(ys), jnp.zeros_like(yi)),
            prob,
            tree,
            sla,
        )
        score_z = jnp.maximum(jnp.maximum(pz, dz), cz)
        use_avg = (score_a < score) & (score_a <= score_z)
        use_zero = (score_z < score) & (score_z < score_a)

        def pick(cur, avg, zero):
            return jnp.where(use_zero, zero, jnp.where(use_avg, avg, cur))

        xn = pick(x, xa, x)
        tn = pick(t, ta, t)
        ytn = pick(yt, yta, jnp.zeros_like(yt))
        ysn = pick(ys, ysa, jnp.zeros_like(ys)) if k else ys
        yin = pick(yi, yia, jnp.zeros_like(yi))
        score_cand = jnp.minimum(jnp.minimum(score, score_a), score_z)
        # log10 bucket of this check's best score (one-hot add: vmap-safe)
        score_b = jnp.clip(
            jnp.floor(
                jnp.log10(jnp.maximum(score_cand, 10.0**KKT_HIST_LO_EXP))
            ).astype(jnp.int32)
            - KKT_HIST_LO_EXP,
            0,
            KKT_HIST_BUCKETS - 1,
        )
        score_hist = c.score_hist + (
            jnp.arange(KKT_HIST_BUCKETS, dtype=jnp.int32) == score_b
        ).astype(jnp.int32)
        pn = pick(p, pa, pz)
        dn = pick(d, da, dz)
        cn = pick(cm, ca, cz)
        done_kkt = (pn < eps_tot) & (dn < eps_tot) & (cn < eps_tot)

        # no-progress / optimal-vertex certificate (termination module): the
        # raw iterate is motionless while the duals tug-of-war, and the
        # t-polished point is primal-feasible.  Only the max-min LP structure
        # (live improvement rows driving a movable t) earns the certificate:
        # there the frozen primal IS the vertex and the polished t is its
        # exact optimum.  A frozen QP iterate has no such optimality
        # evidence, so QP solves (Phase I) never exit this way.
        if use_cert:
            if opts.use_pallas_stats:
                move = jnp.maximum(
                    move_num / (1.0 + move_den),
                    jnp.abs(t - c.pt) / (1.0 + jnp.abs(t)),
                )
            else:
                move = jnp.maximum(
                    jnp.max(jnp.abs(x - c.px)) / (1.0 + jnp.max(jnp.abs(x))),
                    jnp.abs(t - c.pt) / (1.0 + jnp.abs(t)),
                )
            frozen = jnp.where(
                move < opts.noprogress_tol, c.frozen + 1, jnp.zeros((), jnp.int32)
            )
            st_cur = unscale(x, t, yt, ys, yi)
            t_pol = (
                termination.polish_t(st_cur.x, st_cur.t, prob)
                if opts.polish_t
                else st_cur.t
            )
            pres_pol = termination.primal_residual(st_cur.x, t_pol, prob, tree, sla)
            maxmin_lp = (
                jnp.any(jnp.isfinite(prob.imp_lo))
                & (prob.c_t < 0)
                & (sc.t_mov > 0)
            )
            done_vertex = (
                maxmin_lp
                & (frozen >= opts.noprogress_patience)
                & (pres_pol < eps_tot)
                & (~done_kkt)
            )
            # adopt the raw iterate (with the polished t) on a vertex exit;
            # report that adopted state's residuals, not a rejected
            # candidate's
            t_pol_s = jnp.where(sc.t_mov > 0, t_pol / sc.s_t, t)
            xn = jnp.where(done_vertex, x, xn)
            tn = jnp.where(done_vertex, t_pol_s, tn)
            ytn = jnp.where(done_vertex, yt, ytn)
            ysn = jnp.where(done_vertex, ys, ysn) if k else ys
            yin = jnp.where(done_vertex, yi, yin)
            pn = jnp.where(done_vertex, pres_pol, pn)
            dn = jnp.where(done_vertex, d, dn)
            cn = jnp.where(done_vertex, cm, cn)
        else:
            frozen = c.frozen
            done_vertex = jnp.asarray(False)

        done = done_kkt | done_vertex

        chunk = c.chunk + 1
        chunks_since = c.chunks_since + 1
        do_restart, stall, stalled = restarts_mod.restart_decision(
            score_cand,
            c.score_prev,
            c.score_restart,
            chunks_since,
            c.stall,
            beta_suff=opts.restart_beta_suff,
            beta_nec=opts.restart_beta_nec,
            stall_checks=opts.stall_checks,
            restart_every=opts.restart_every,
            adaptive=opts.adaptive_restarts,
        )
        do_restart = do_restart & (~done)

        # primal-weight re-estimate: travel ratio since the anchor, or
        # residual balance when the stall detector fired
        if opts.use_pallas_stats:
            # select the fused travel partial matching the adopted candidate
            # (the vertex exit keeps the raw iterate, but a vertex exit is
            # `done`, which suppresses the restart that would consume dx/dy)
            dx = jnp.sqrt(pick(dx2_cur, dx2_avg, dx2_cur))
            dy = jnp.sqrt(
                pick(dyt2_cur, dyt2_avg, dyt2_zero)
                + pick(dyi2_cur, dyi2_avg, dyi2_zero)
            )
        else:
            dx = jnp.sqrt(jnp.sum((xn - c.rx) ** 2))
            dy = jnp.sqrt(
                jnp.sum((ytn - c.ry_tree) ** 2) + jnp.sum((yin - c.ry_imp) ** 2)
            )
        if use_blockwise:
            dy_sla = jnp.sqrt(jnp.sum((ysn - c.ry_sla) ** 2))
            om_up, om_sla_up = restarts_mod.update_omega_blocks(
                om, om_sla, dx, dy, dy_sla, pn, dn, cn, stalled
            )
            om_new = jnp.where(do_restart, om_up, om)
            om_sla_new = jnp.where(do_restart, om_sla_up, om_sla)
        else:
            om_new = jnp.where(
                do_restart,
                restarts_mod.update_omega(om, dx, dy, pn, dn, cn, stalled),
                om,
            )
            om_sla_new = om_sla

        # on restart (or exit) adopt the candidate; otherwise keep iterating
        # from the raw iterate
        adopt = do_restart | done
        x_out = jnp.where(adopt, xn, x)
        t_out = jnp.where(adopt, tn, t)
        yt_out = jnp.where(adopt, ytn, yt)
        ys_out = jnp.where(adopt, ysn, ys) if k else ys
        yi_out = jnp.where(adopt, yin, yi)

        def zf(arr):
            return jnp.where(do_restart, jnp.zeros_like(arr), arr)

        return Carry(
            x=x_out,
            t=t_out,
            y_tree=yt_out,
            y_sla=ys_out,
            y_imp=yi_out,
            omega=om_new,
            omega_sla=om_sla_new,
            ax=zf(ax),
            at=zf(at_),
            ayt=zf(ayt),
            ays=zf(ays),
            ayi=zf(ayi),
            acount=jnp.where(do_restart, 0.0, cnt),
            rx=jnp.where(do_restart, x_out, c.rx),
            ry_tree=jnp.where(do_restart, yt_out, c.ry_tree),
            ry_sla=jnp.where(do_restart, ys_out, c.ry_sla),
            ry_imp=jnp.where(do_restart, yi_out, c.ry_imp),
            px=x,
            pt=t,
            chunk=chunk,
            pres=pn,
            dres=dn,
            cres=cn,
            score_prev=score_cand,
            # the first check anchors the restart score without restarting
            # (PDLP anchors at the initial point); each restart re-anchors
            score_restart=jnp.where(
                do_restart,
                score_cand,
                jnp.where(jnp.isfinite(c.score_restart), c.score_restart, score_cand),
            ),
            chunks_since=jnp.where(do_restart, 0, chunks_since),
            stall=stall,
            frozen=frozen,
            restarts=c.restarts + do_restart.astype(jnp.int32),
            done=done,
            certified=done_kkt,
            score_hist=score_hist,
        )

    final = lax.while_loop(cond, body, c0)
    # return state in original units
    state = unscale(final.x, final.t, final.y_tree, final.y_sla, final.y_imp)
    if opts.polish_t:
        # hand back the exact epigraph t for the returned x on EVERY
        # max-min exit (polish_t is the identity for QPs): a certified exit
        # satisfies the relative KKT tolerance but its scalar can still sit
        # O(eps * scale) watts off the optimum the settled x determines in
        # closed form, and an uncertified max_iters exit inflates t further
        state = state._replace(t=termination.polish_t(state.x, state.t, prob))
    stats = SolveStats(
        iterations=final.chunk * opts.check_every,
        primal_res=final.pres,
        dual_res=final.dres,
        comp_res=final.cres,
        converged=final.done,
        omega=final.omega,
        certified=final.certified,
        restarts=final.restarts,
        score_hist=final.score_hist,
    )
    return state, stats
