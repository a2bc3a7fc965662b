"""What every system adapter (``systems/<name>.py``) shares: the deployment's
tree, built through the program's own PDN types from the configuration's
sizes, and the answer one control interval returns."""

from __future__ import annotations

from typing import NamedTuple

import numpy as np
from repro.pdn.tree import FlatPDN, PDNNode, flatten


class Answer(NamedTuple):
    """One interval's caps as host numpy: the final ones and those of Phases
    I and II."""

    allocation: np.ndarray
    phase1: np.ndarray
    phase2: np.ndarray
    pdhg_iters: int  # PDHG iterations, summed over phases


def uniform_pdn(cfg: dict) -> FlatPDN:
    """The uniform tree of ``cfg``: ``fanout[d]`` children per node at depth
    ``d`` (the last entry is devices per leaf node), each node's cap
    ``oversub[d]`` times the sum of its children's caps."""
    fanout, oversub = cfg["fanout"], cfg["oversub"]

    def node(d: int) -> PDNNode:
        if d == len(fanout) - 1:
            return PDNNode(
                capacity=float(oversub[d]) * fanout[d] * cfg["u"], n_devices=fanout[d]
            )
        kids = [node(d + 1) for _ in range(fanout[d])]
        n = PDNNode(capacity=float(oversub[d]) * fanout[d] * kids[0].capacity)
        n.children = kids
        return n

    return flatten(node(0), default_l=cfg["l"], default_u=cfg["u"])
