"""Plain reference of the control step: the three nvPAX phases on a uniform
power-distribution tree, solved exactly, in numpy.

It imports nothing of the program under test.  The tree is rebuilt from the
configuration's sizes (``fanout`` and ``oversub``, root first), so no
topology, cap or table the program made reaches it.

Every phase is a projection onto the same polytope: each device in a box
``[lo_i, hi_i]``, every subtree's sum at most its cap.  For a separable
quadratic over a tree of nested caps the optimum is

    x_i = clip(r_i - price_i, lo_i, hi_i),   price_i = max(0, mu_v for v above i)

where ``mu_v`` is the price at which node ``v`` alone would meet its cap
given the prices already found below it.  The nodes are solved bottom-up,
each by bisection on its own price; after a level is solved its prices are
folded into the device upper bounds, so the next level solves the same
kind of equation.

- Phase I (paper eq. 4 with the pin-free simplification of section 4.3.1):
  track the shaped request on active devices, idle devices held at ``l``.
- Phases II and III (the lexicographic max-min raise of Algorithm 2): on a
  polymatroid the max-min fair raise is the least-norm raise, so each is the
  projection of ``base + T`` with ``T`` above every device's head-room, the
  raised set boxed in ``[base, u]`` and every other device held at ``base``.
  Phase II raises the active devices, Phase III the idle ones.
"""

from __future__ import annotations

import numpy as np

BISECT_STEPS = 80  # halves any bracket below 1e8 W to under an ulp


class Tree:
    """A uniform tree: ``fanout[d]`` children per node at depth ``d`` (the
    last entry is devices per leaf node); a node's cap is ``oversub[d]``
    times the sum of its children's caps, and a device's cap is ``u``."""

    def __init__(self, fanout, oversub, l, u, dtype=np.float64):
        if len(fanout) != len(oversub):
            raise ValueError("fanout and oversub need one entry per node level")
        self.fanout = [int(f) for f in fanout]
        self.n = int(np.prod(self.fanout))
        self.dtype = np.dtype(dtype)
        self.l = np.full(self.n, l, self.dtype)
        self.u = np.full(self.n, u, self.dtype)
        # block[d]: devices under one node at depth d; cap[d]: that node's cap
        self.block = [int(np.prod(self.fanout[d:])) for d in range(len(self.fanout))]
        caps = [0.0] * len(self.fanout)
        child = float(u)
        for d in reversed(range(len(self.fanout))):
            caps[d] = float(oversub[d]) * self.fanout[d] * child
            child = caps[d]
        self.cap = caps

    def node_caps(self, depth: int) -> np.ndarray:
        return np.full(self.n // self.block[depth], self.cap[depth], self.dtype)


def _node_prices(r, lo, hi, cap):
    """Per row of ``[K, B]``: the least ``mu >= 0`` with
    ``sum(clip(r - mu, lo, hi)) <= cap``, by bisection."""
    dt = r.dtype
    need = np.clip(r, lo, hi).sum(axis=1) > cap
    mu_lo = np.zeros(r.shape[0], dt)
    mu_hi = np.maximum((r - lo).max(axis=1), 0).astype(dt)
    for _ in range(BISECT_STEPS):
        mid = (mu_lo + mu_hi) * dt.type(0.5)
        over = np.clip(r - mid[:, None], lo, hi).sum(axis=1) > cap
        mu_lo = np.where(over, mid, mu_lo)
        mu_hi = np.where(over, mu_hi, mid)
    return np.where(need, mu_hi, dt.type(0))


def project(tree: Tree, target, lo, hi):
    """Least-squares projection of ``target`` onto the box ``[lo, hi]`` and
    every node cap of ``tree``."""
    dt = tree.dtype
    r = np.asarray(target, dt)
    lo = np.asarray(lo, dt)
    hi = np.clip(r, lo, np.asarray(hi, dt))  # the upper bound at price 0
    for d in reversed(range(len(tree.fanout))):
        caps = tree.node_caps(d)
        B = tree.block[d]
        R, L, H = (a.reshape(-1, B) for a in (r, lo, hi))
        mu = _node_prices(R, L, H, caps)
        hi = np.clip(R - mu[:, None], L, H).reshape(-1)
    return hi


def three_phase(tree: Tree, telemetry, idle_threshold):
    """(x1, x2, x3) of the three phases for one control interval."""
    dt = tree.dtype
    tele = np.asarray(telemetry, dt)
    l, u = tree.l, tree.u
    active = tele >= idle_threshold
    req = np.where(active, np.clip(tele, l, u), l)
    raise_by = dt.type(2.0) * (u - l).max()  # above every device's head-room
    x1 = project(tree, req, l, np.where(active, u, l))
    x2 = project(tree, x1 + raise_by, x1, np.where(active, u, x1))
    x3 = project(tree, x2 + raise_by, x2, np.where(active, x2, u))
    return x1, x2, x3
