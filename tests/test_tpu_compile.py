"""Compile the main path for a described TPU v5e, with no chip attached.

Nothing runs here: these tests hand the TPU compiler the engine's control
step and the PDHG update kernels at their real shapes, so a program or a
kernel the v5e compiler refuses fails on the CPU, before a chip run.  This
is the only test file that describes the chip.  The topology is described
inside a fixture, never on import: only one process at a time may load the
TPU library, and a test worker that loads it keeps it until it exits.
"""

from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

WIDTH = 12_288  # the paper's production hall


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from jax.experimental import topologies

        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
    )


def test_engine_step_compiles_for_v5e(small_pdn, one_chip):
    """The float64 cold control step of ``AllocEngine`` (the program
    ``AllocEngine.step`` dispatches first)."""
    from repro.core.engine import AllocEngine, _engine_step_jit

    eng = AllocEngine(small_pdn)
    n = small_pdn.n
    with jax.enable_x64(True):
        fleet = _shapes(eng.fleet, one_chip)
        r = jax.ShapeDtypeStruct((n,), jnp.float64, sharding=one_chip)
        priority = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
        active = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)
        compiled = _engine_step_jit.lower(
            fleet, r, priority, active, None, None, None, None,
            meta=eng.meta, opts=eng.options.solver,
        ).compile()
    assert compiled.output_shardings[2].device_set == one_chip.device_set


@pytest.mark.parametrize("kernel", ["primal_update", "dual_prox"])
def test_pdhg_kernel_compiles_for_v5e(kernel, one_chip):
    """The two PDHG update kernels the v5e compiler accepts, lowered for
    Mosaic (``interpret=False``: this process's backend is the CPU, where
    the default would pick the interpreter)."""
    from repro.kernels.pdhg_update import dual_prox, primal_update

    vec = jax.ShapeDtypeStruct((WIDTH,), jnp.float32, sharding=one_chip)
    if kernel == "primal_update":
        fn, args = primal_update, (vec,) * 8
    else:
        fn, args = dual_prox, (vec,) * 5
    compiled = fn.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_fleet_step_compiles_for_a_v5e_mesh(topo):
    """The float64 cold step of the sharded fleet dispatch on a 2x2 mesh,
    two domains a chip: the coordinator's ``psum`` must reach the compiled
    program as a collective, and the caps stay sharded over the domains."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.fleet import FleetOrchestrator
    from repro.fleet import sharded as shd
    from repro.pdn.hierarchy_gen import homogeneous_fleet

    mesh = Mesh(np.array(topo.devices), ("domains",))
    split, whole = NamedSharding(mesh, P("domains")), NamedSharding(mesh, P())
    pdn = homogeneous_fleet(8, racks_per_domain=1, servers_per_rack=2, gpus_per_server=4)
    orch = FleetOrchestrator(pdn, level=1, mode="stacked")  # host mirrors only
    k, n, m = orch.k, orch._N, orch._M
    with jax.enable_x64(True):
        rep, rowmap = orch._sharded_plan()
        compiled = shd._step_jit.lower(
            _shapes(orch._dom, split),
            jax.ShapeDtypeStruct((k, m), jnp.float64, sharding=split),
            jax.ShapeDtypeStruct((k, n), jnp.float64, sharding=split),
            jax.ShapeDtypeStruct((k, n), jnp.bool_, sharding=split),
            rowmap, None, None, _shapes(rep, whole), None,
            mesh=mesh, meta=orch.meta, opts=orch.options.solver,
            coord_mode="waterfill", rec_cfg=None,
        ).compile()
    text = compiled.as_text()
    assert "all-gather" in text or "all-reduce" in text
    x1, x2, x3 = compiled.output_shardings[:3]
    assert x1 == x2 == x3 == split
