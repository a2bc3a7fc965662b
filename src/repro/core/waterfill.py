"""Exact tree water-filling: the max-min phases when no tenant SLAs are
present.

Progressive filling: raise all unsaturated devices in the optimized set at a
uniform rate; when a device bound or node capacity binds, freeze the affected
devices; repeat.  For box + tree-capacity feasible sets this produces the
lexicographically max-min optimal allocation — the same limit the paper's
iterated LP sequence (Algorithm 2) converges to.  Per-round cost is
O(n + m); the number of rounds is the number of distinct binding events,
devices reaching their own bound included (up to ~1,300 on a 12k hall).

Three fills share these semantics:

* :func:`waterfill_arrays` — the sweep in numpy: the fleet coordinator's
  host plan (:meth:`repro.fleet.coordinator.BudgetCoordinator.plan`) and
  the oracle the tests check the others against;
* :func:`waterfill_jax` — the sweep as a ``lax.while_loop``: the fleet
  coordinator's grant plans (:mod:`repro.fleet.coordinator`,
  :mod:`repro.fleet.sharded`);
* :func:`waterfill_project_jax` — the same raise as a level-wise tree
  projection, whose cost follows the tree levels that bind, not the
  devices that saturate: the jitted step's Phases II/III
  (:func:`repro.core.batched.solve_three_phase`, so ``AllocEngine`` and
  the fleet orchestrator's domain solves).

Phase I shares the fills' level-wise search: :func:`tree_project_jax` is the
weighted least-squares projection of the requests onto the box and the
caps, Phase I's level QP solved exactly where no tenant rows exist
(:func:`repro.core.batched.solve_three_phase`).
"""

from __future__ import annotations

import numpy as np

from repro.pdn.tree import FlatPDN

__all__ = [
    "tree_project_jax",
    "waterfill",
    "waterfill_arrays",
    "waterfill_jax",
    "waterfill_project_jax",
]


def waterfill_arrays(
    start: np.ndarray,
    end: np.ndarray,
    cap: np.ndarray,
    u: np.ndarray,
    base: np.ndarray,
    opt_mask: np.ndarray,
    max_rounds: int = 10_000,
) -> np.ndarray:
    """Max-min raise of ``base`` over devices in ``opt_mask``; all other
    devices stay fixed at ``base``.  Requires no tenant constraints.

    ``start``/``end``/``cap`` describe DFS-contiguous tree nodes; ``u`` is
    the per-device upper limit.
    """
    n = base.shape[0]
    x = np.asarray(base, dtype=np.float64).copy()
    live = np.asarray(opt_mask, dtype=bool).copy()

    for _ in range(max_rounds):
        if not live.any():
            break
        lv = live.astype(np.float64)
        ccs = np.concatenate([[0.0], np.cumsum(lv)])
        n_live = ccs[end] - ccs[start]  # live devices under each node
        xcs = np.concatenate([[0.0], np.cumsum(x)])
        sums = xcs[end] - xcs[start]
        slack = cap - sums
        with np.errstate(divide="ignore", invalid="ignore"):
            node_rate = np.where(n_live > 0, slack / np.maximum(n_live, 1), np.inf)
        dev_rate = np.where(live, u - x, np.inf)
        t = min(node_rate.min(), dev_rate.min())
        t = max(t, 0.0)
        if not np.isfinite(t):
            break
        x = np.where(live, x + t, x)
        # freeze: devices at u, or under any node now tight.  The nodes and
        # devices whose rate set t are frozen by that comparison, not by the
        # recomputed sums: the sums' rounding can exceed the 1e-9 tolerance,
        # and a round that froze nothing would end the sweep early.
        xcs = np.concatenate([[0.0], np.cumsum(x)])
        sums = xcs[end] - xcs[start]
        tight = ((node_rate <= t) | (cap - sums <= 1e-9)) & (n_live > 0)
        under_tight = np.zeros(n + 1)
        np.add.at(under_tight, start[tight], 1.0)
        np.add.at(under_tight, end[tight], -1.0)
        under_tight = np.cumsum(under_tight)[:n] > 0
        newly = live & ((dev_rate <= t) | (u - x <= 1e-9) | under_tight)
        if not newly.any():
            break  # unbounded direction fully absorbed (all at u) or stalled
        live &= ~newly
    return x


def waterfill_jax(base, opt_mask, tree, u, max_rounds: int = 10_000):
    """Trace-safe :func:`waterfill_arrays`: the progressive-filling sweep as
    a ``lax.while_loop``, usable inside jit/vmap (the batched engine's
    max-min fast path on SLA-free problems).

    ``tree`` is a :class:`repro.core.treeops.TreeTopo`; semantics and
    freezing order mirror the numpy sweep exactly (cross-validated in
    tests), so host and jitted paths produce the same allocation.

    Returns ``(x, rounds)``: the allocation and the sweep's int32 round
    count, which the loop carries anyway.  The sweep runs under the
    ``waterfill`` named scope, so a device profile can attribute its ops.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.core.treeops import tree_matvec, tree_rmatvec

    n = base.shape[0]
    x0 = jnp.asarray(base)
    dtype = x0.dtype
    live0 = jnp.asarray(opt_mask, bool)
    u = jnp.asarray(u, dtype)

    def cond(carry):
        _, live, done, rounds = carry
        return (~done) & jnp.any(live) & (rounds < max_rounds)

    def body(carry):
        x, live, _, rounds = carry
        lv = live.astype(dtype)
        n_live = tree_matvec(lv, tree)
        slack = tree.cap - tree_matvec(x, tree)
        node_rate = jnp.where(n_live > 0, slack / jnp.maximum(n_live, 1.0), jnp.inf)
        dev_rate = jnp.where(live, u - x, jnp.inf)
        t = jnp.maximum(jnp.minimum(jnp.min(node_rate), jnp.min(dev_rate)), 0.0)
        finite = jnp.isfinite(t)
        # numpy sweep breaks BEFORE applying a non-finite raise
        x_new = jnp.where(live & finite, x + t, x)
        # freeze: devices at u, or under any node now tight (the rates that
        # set t freeze exactly, as in the numpy sweep)
        slack_new = tree.cap - tree_matvec(x_new, tree)
        tight = ((node_rate <= t) | (slack_new <= 1e-9)) & (n_live > 0)
        under_tight = tree_rmatvec(tight.astype(dtype), tree, n) > 0.5
        newly = live & ((dev_rate <= t) | (u - x_new <= 1e-9) | under_tight)
        stalled = ~jnp.any(newly)  # unbounded direction absorbed or stalled
        done = (~finite) | stalled
        live_new = jnp.where(finite, live & ~newly, live)
        return x_new, live_new, done, rounds + 1

    with jax.named_scope("waterfill"):
        x, _, _, rounds = lax.while_loop(
            cond, body, (x0, live0, jnp.asarray(False), jnp.asarray(0, jnp.int32))
        )
    return x, rounds


# Water levels tried per search step of waterfill_project_jax, as one
# [SEARCH_CANDIDATES, n] evaluation that cuts a node's bracket 33-fold.  At
# n = 12,288 in float64 on a TPU v5e a step costs ~0.35 ms at 24-64
# candidates, while 16 compile to ~7x the code and cost 0.60 ms a step.
SEARCH_CANDIDATES = 32
SEARCH_STEP_CAP = 64  # per tree level; a bracket reaches one ulp in ~11-15


def waterfill_project_jax(base, opt_mask, tree, u, n_depths: int):
    """The max-min raise of :func:`waterfill_arrays` as a tree projection,
    trace-safe under jit and vmap: its cost follows how many tree levels
    bind, not how many devices saturate.

    On a polymatroid (box plus nested caps) the max-min fair raise is the
    least-norm raise: the projection of ``base + T`` (``T`` at or above every
    raised device's head-room) onto the box ``[base, u]`` of the raised
    devices (``opt_mask``; every other device held at ``base``) and the
    tree's caps.  It is solved bottom-up, one tree level at a time, with
    one water level ``lam = T - price`` per node: the largest ``lam >= 0``
    with ``sum(min(lam, h_i)) <= cap - sum(base)`` over the node's devices,
    ``h_i`` being each device's head-room with the deeper levels' water
    levels folded in.  The level is found by a bracketing search over
    ``SEARCH_CANDIDATES`` levels per step, vectorised over the nodes of a
    tree level, until no bracket shrinks in the dtype; the feasible end is
    kept, so every subtree sum stays at or below its cap, and a node over
    its cap at entry leaves its raised devices at ``base``.  A tree level
    where no node binds runs no search step.

    ``n_depths`` (static) is the number of tree levels, root included.
    Returns ``(x, steps, levels)``: the allocation, the search steps run
    (int32; what the fill's time scales with) and the tree levels whose
    search ran.  Runs under the ``waterfill`` named scope.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.core.treeops import tree_matvec, tree_rmatvec

    x0 = jnp.asarray(base)
    dtype = x0.dtype
    n, m = x0.shape[0], tree.m
    u = jnp.asarray(u, dtype)
    zero = jnp.zeros((), jnp.int32)
    frac = jnp.arange(1, SEARCH_CANDIDATES + 1, dtype=dtype) / (SEARCH_CANDIDATES + 1)
    pad = jnp.zeros((SEARCH_CANDIDATES, 1), dtype)
    inf = jnp.asarray(jnp.inf, dtype)

    with jax.named_scope("waterfill"):
        h0 = jnp.where(jnp.asarray(opt_mask, bool), jnp.maximum(u - x0, 0.0), 0.0)
        room = tree.cap - tree_matvec(x0, tree)
        # a node that holds all its devices at their upper bounds (a server
        # carrying 8 x u) never binds, however its sums round
        can_bind = tree_matvec(jnp.maximum(u, x0), tree) > tree.cap
        # anc[d, i]: the depth-d node above device i, or m (the slot past
        # the last node) where none is; exact, as an integer prefix sum
        ids = jnp.where(
            tree.depth[None, :] == jnp.arange(n_depths, dtype=jnp.int32)[:, None],
            jnp.arange(1, m + 1, dtype=jnp.int32),
            0,
        )
        anc = jax.vmap(lambda y: tree_rmatvec(y, tree, n))(ids) - 1
        anc = jnp.where(anc < 0, m, anc)

        def level(k, carry):
            h, steps, levels = carry
            d = n_depths - 1 - k  # deepest level first
            above = anc[d]
            need = tree_matvec(h, tree)  # subtree sums at lam = +inf
            bind = (tree.depth == d) & can_bind & (need > 0) & (need > room)
            # bracket [lo, hi]: lam = lo fits under the cap, hi overshoots it
            lo = jnp.zeros((m,), dtype)
            hi = jnp.where(bind & (room > 0), jnp.max(h), 0.0)

            def cond(c):
                _, _, live, s = c
                return jnp.any(live) & (s < SEARCH_STEP_CAP)

            def body(c):
                lo, hi, live, s = c
                cand = lo + (hi - lo) * frac[:, None]  # [SEARCH_CANDIDATES, m]
                fill = jnp.minimum(jnp.concatenate([cand, pad], axis=1)[:, above], h)
                fits = jax.vmap(lambda v: tree_matvec(v, tree))(fill) <= room
                lo_new = jnp.maximum(lo, jnp.max(jnp.where(fits, cand, -inf), axis=0))
                hi_new = jnp.minimum(hi, jnp.min(jnp.where(fits, inf, cand), axis=0))
                hi_new = jnp.maximum(hi_new, lo_new)  # rounding may cross them
                shrank = (lo_new != lo) | (hi_new != hi)
                return (
                    jnp.where(live, lo_new, lo),
                    jnp.where(live, hi_new, hi),
                    live & shrank & (hi_new > lo_new),
                    s + 1,
                )

            init = (lo, hi, bind & (hi > lo), zero)
            lam, _, _, s = lax.while_loop(cond, body, init)
            lam = jnp.concatenate([jnp.where(bind, lam, inf), inf[None]])
            return jnp.minimum(h, lam[above]), steps + s, levels + (s > 0)

        h, steps, levels = lax.fori_loop(0, n_depths, level, (h0, zero, zero))
        x = jnp.where(h > 0, jnp.minimum(x0 + h, u), x0)
    return x, steps, levels


def tree_project_jax(target, w, lo, hi, tree, n_depths: int):
    """Weighted least-squares projection of ``target`` onto the box
    ``[lo, hi]`` and the tree's caps, trace-safe under jit and vmap: Phase
    I's level QP (paper eq. 4) solved exactly on problems with no tenant
    rows.

    It minimises ``sum(w_i * (x_i - target_i)**2)``.  At a node price ``P``
    a device answers ``clip(target_i - P / w_i, lo_i, hi_i)``, and a device
    carries the largest price of any node above it.  The prices are found
    bottom-up, one tree level at a time: a node binds where its devices'
    answers at the deeper levels' prices overshoot its cap, and its price is
    the least ``P`` whose answers fit, found by a bracketing search over
    ``SEARCH_CANDIDATES`` prices a step, vectorised over the nodes of a tree
    level, until no bracket shrinks in the dtype.  The feasible (higher
    price) end is kept, so every subtree sum stays at or below its cap up to
    rounding; a node over its cap with every device at ``lo`` leaves them at
    ``lo`` (the caller's repair does the rest).  Devices with ``w_i == 0``
    are pinned (``lo == hi``).  A tree level where no node binds runs no
    search step.

    ``n_depths`` (static) is the number of tree levels, root included.
    Returns ``(x, steps, levels)``: the projection, the search steps run
    (int32; what its time scales with) and the tree levels whose search
    ran.  Runs under the ``project`` named scope.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.core.treeops import tree_matvec, tree_rmatvec

    target = jnp.asarray(target)
    dtype = target.dtype
    n, m = target.shape[0], tree.m
    w, lo, hi = (jnp.asarray(a, dtype) for a in (w, lo, hi))
    zero = jnp.zeros((), jnp.int32)
    frac = jnp.arange(1, SEARCH_CANDIDATES + 1, dtype=dtype) / (SEARCH_CANDIDATES + 1)
    pad = jnp.zeros((SEARCH_CANDIDATES, 1), dtype)
    inf = jnp.asarray(jnp.inf, dtype)
    weighted = w > 0
    inv_w = jnp.where(weighted, 1.0 / jnp.where(weighted, w, 1.0), 0.0)

    def answer(price):
        """Each device's value at a price (``+inf`` puts it at ``lo``)."""
        return jnp.where(weighted, jnp.clip(target - price * inv_w, lo, hi), lo)

    with jax.named_scope("project"):
        x0 = answer(jnp.zeros((), dtype))
        # a node whose devices all fit at their upper bounds never binds
        can_bind = tree_matvec(jnp.maximum(hi, lo), tree) > tree.cap
        room = tree_matvec(lo, tree) < tree.cap
        # above every price that moves a device: at 2 * p_top each weighted
        # device answers lo
        p_top = 2.0 * jnp.max(jnp.where(weighted, w * (target - lo), 0.0), initial=0.0)
        # anc[d, i]: the depth-d node above device i, or m (the slot past
        # the last node) where none is; exact, as an integer prefix sum
        ids = jnp.where(
            tree.depth[None, :] == jnp.arange(n_depths, dtype=jnp.int32)[:, None],
            jnp.arange(1, m + 1, dtype=jnp.int32),
            0,
        )
        anc = jax.vmap(lambda y: tree_rmatvec(y, tree, n))(ids) - 1
        anc = jnp.where(anc < 0, m, anc)

        def level(k, carry):
            x, steps, levels = carry
            d = n_depths - 1 - k  # deepest level first
            above = anc[d]
            bind = (tree.depth == d) & can_bind & (tree_matvec(x, tree) > tree.cap)
            # bracket [p_lo, p_hi]: p_hi fits under the cap, p_lo overshoots
            p_lo = jnp.zeros((m,), dtype)
            p_hi = jnp.where(bind & room, p_top, 0.0)

            def cond(c):
                _, _, live, s = c
                return jnp.any(live) & (s < SEARCH_STEP_CAP)

            def body(c):
                p_lo, p_hi, live, s = c
                cand = p_lo + (p_hi - p_lo) * frac[:, None]  # [SEARCH_CANDIDATES, m]
                price = jnp.concatenate([cand, pad], axis=1)[:, above]
                fill = jnp.minimum(answer(price), x)
                fits = jax.vmap(lambda v: tree_matvec(v, tree))(fill) <= tree.cap
                hi_new = jnp.minimum(p_hi, jnp.min(jnp.where(fits, cand, inf), axis=0))
                lo_new = jnp.maximum(p_lo, jnp.max(jnp.where(fits, -inf, cand), axis=0))
                lo_new = jnp.minimum(lo_new, hi_new)  # rounding may cross them
                shrank = (lo_new != p_lo) | (hi_new != p_hi)
                return (
                    jnp.where(live, lo_new, p_lo),
                    jnp.where(live, hi_new, p_hi),
                    live & shrank & (hi_new > lo_new),
                    s + 1,
                )

            init = (p_lo, p_hi, bind & room & (p_hi > p_lo), zero)
            _, p, _, s = lax.while_loop(cond, body, init)
            # a node that cannot fit even at lo prices its devices to lo
            p = jnp.where(bind, jnp.where(room, p, inf), 0.0)
            p = jnp.concatenate([p, jnp.zeros((1,), dtype)])
            return jnp.minimum(x, answer(p[above])), steps + s, levels + (s > 0)

        x, steps, levels = lax.fori_loop(0, n_depths, level, (x0, zero, zero))
    return x, steps, levels


def waterfill(
    pdn: FlatPDN,
    base: np.ndarray,
    opt_mask: np.ndarray,
    max_rounds: int = 10_000,
) -> np.ndarray:
    """FlatPDN convenience wrapper around :func:`waterfill_arrays`."""
    return waterfill_arrays(
        pdn.node_start, pdn.node_end, pdn.node_cap, pdn.dev_u, base, opt_mask,
        max_rounds,
    )
