"""The comparison that decides ``correct``, and the user-facing arithmetic
the end-to-end metrics share with it.

- Exact subtree sums: ``math.fsum`` per node, as in the repo's
  ``chip_smoke.py`` (``subtree_sums``): a prefix-sum difference loses about
  1e-6 W to rounding at 1e5 devices, the size of the bar it checks.
- Satisfaction: the paper's S = sum(min(r, a)) / sum(r), copied from
  ``src/repro/core/metrics.py`` (``satisfaction_ratio``).

Numbers compared, each against its limit in the configuration's ``limits``:

- ``excess_w``: the most by which any cap is exceeded, over the sampled
  intervals and every phase the system returns: a device above ``u`` or
  below ``l``, or a subtree above its cap (the configuration's guarantees).
- ``gap_w``: the largest per-device distance of the final caps from the
  plain reference's.
- ``total_gap_w``: the largest distance of the whole tree's total from the
  reference's, over Phase II and the final caps.
"""

from __future__ import annotations

import math

import numpy as np
import reference


def shaped_requests(cfg: dict, telemetry: np.ndarray) -> np.ndarray:
    """Paper section 5.2: idle devices request ``l``, the rest their clipped
    telemetry."""
    l, u = cfg["l"], cfg["u"]
    return np.where(
        telemetry >= cfg["idle_threshold"], np.clip(telemetry, l, u), l
    )


def satisfaction(requests: np.ndarray, alloc: np.ndarray) -> float:
    tot = float(requests.sum())
    if tot <= 0:
        return 1.0
    return float(np.minimum(requests, alloc).sum()) / tot


def block_sums(x: np.ndarray, block: int) -> np.ndarray:
    """Exact sum of each consecutive ``block`` of ``x``."""
    return np.array([math.fsum(row) for row in x.reshape(-1, block)])


def excess(tree: reference.Tree, x: np.ndarray) -> float:
    """The most by which ``x`` exceeds a device box or a subtree cap."""
    worst = max(float(np.max(tree.l - x)), float(np.max(x - tree.u)))
    for d, block in enumerate(tree.block):
        worst = max(worst, float(np.max(block_sums(x, block) - tree.cap[d])))
    return worst


def compare(cfg: dict, answers: list) -> dict[str, float]:
    """Worst readings over ``answers``: (telemetry, Answer) pairs."""
    tree = reference.Tree(cfg["fanout"], cfg["oversub"], cfg["l"], cfg["u"])
    out = {"excess_w": 0.0, "gap_w": 0.0, "total_gap_w": 0.0}
    for tele, ans in answers:
        phases = [np.asarray(a, float) for a in (ans.phase1, ans.phase2, ans.allocation)]
        if not all(np.isfinite(a).all() for a in phases):
            return {k: math.inf for k in out}
        _, r2, r3 = reference.three_phase(tree, tele, cfg["idle_threshold"])
        for x in phases:
            out["excess_w"] = max(out["excess_w"], excess(tree, x))
        out["gap_w"] = max(out["gap_w"], float(np.max(np.abs(phases[2] - r3))))
        for x, r in ((phases[1], r2), (phases[2], r3)):
            gap = abs(math.fsum(x) - math.fsum(r))
            out["total_gap_w"] = max(out["total_gap_w"], gap)
    return out


def verdict(compared: dict[str, float], limits: dict[str, float]) -> bool:
    return all(compared[k] <= limits[k] for k in compared)
