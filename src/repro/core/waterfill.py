"""Exact tree water-filling: a combinatorial oracle (and fast path) for the
max-min phases when no tenant SLAs are present.

Progressive filling: raise all unsaturated devices in the optimized set at a
uniform rate; when a device bound or node capacity binds, freeze the affected
devices; repeat.  For box + tree-capacity feasible sets this produces the
lexicographically max-min optimal allocation — the same limit the paper's
iterated LP sequence (Algorithm 2) converges to.  Used (a) in tests to
cross-validate Phases II/III against the LP path and (b) as the production
fast path on the controller hot loop for SLA-free problems (a beyond-paper
optimization recorded in EXPERIMENTS.md §Perf: it replaces an iterated
50k-iteration LP solve at n = 12k with an exact O(depth * n * rounds) sweep).

Per-round cost is O(n + m); the number of rounds is bounded by the number of
distinct binding events (<= number of nodes + 1), and in practice is ~tree
depth.
"""

from __future__ import annotations

import numpy as np

from repro.pdn.tree import FlatPDN

__all__ = ["waterfill", "waterfill_arrays", "waterfill_jax"]


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
