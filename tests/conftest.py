"""Shared fixtures.

NOTE: do NOT set XLA_FLAGS / host-device-count here — smoke tests and
benchmarks must see the real single CPU device; only launch/dryrun.py forces
512 placeholder devices (and only in its own process).
"""

from __future__ import annotations

import numpy as np
import pytest


@pytest.fixture(scope="session")
def x64():
    """Enable float64 for the duration of a test (context-managed)."""
    import jax

    with jax.enable_x64(True):
        yield


@pytest.fixture(scope="session")
def small_pdn():
    """2 halls x 3 racks x 2 servers x 4 devices = 48 devices, oversub 0.85."""
    from repro.pdn.tree import build_from_level_sizes

    return build_from_level_sizes([2, 3, 2], gpus_per_server=4)


@pytest.fixture(scope="session")
def tiny_pdn():
    """Root + 2 servers x 4 devices = 8 devices."""
    from repro.pdn.tree import PDNNode, flatten

    root = PDNNode(capacity=4000.0)
    root.add(PDNNode(capacity=2400.0, n_devices=4))
    root.add(PDNNode(capacity=2400.0, n_devices=4))
    return flatten(root, default_l=100.0, default_u=700.0)


def rand_requests(pdn, seed=0, lo=50.0, hi=800.0):
    return np.random.default_rng(seed).uniform(lo, hi, pdn.n)
