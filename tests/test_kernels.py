"""Per-kernel interpret-mode validation: shape/dtype sweeps asserting
allclose against the pure-jnp oracles in each kernel's ref.py."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.pdhg_update import (
    dual_chunk_stats,
    dual_prox,
    primal_chunk_stats,
    primal_update,
)
from repro.kernels.pdhg_update.ref import (
    dual_chunk_stats_ref,
    dual_prox_ref,
    primal_chunk_stats_ref,
    primal_update_ref,
)
from repro.kernels.tree_matvec import (
    sla_matvec,
    sla_rmatvec,
    tree_matvec,
    tree_rmatvec,
)
from repro.kernels.tree_matvec.ref import (
    sla_matvec_ref,
    sla_rmatvec_ref,
    tree_matvec_ref,
    tree_rmatvec_ref,
)
from repro.pdn.tree import build_from_level_sizes


# ---------------------------------------------------------------------------
# pdhg_update
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [7, 128, 8192, 20000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("vector_tau", [False, True])
def test_primal_update_sweep(n, dtype, vector_tau):
    """Scalar steps (uniform fallback) and per-variable step vectors (the
    preconditioned form the solver core streams) both match the oracle."""
    with jax.enable_x64(dtype == jnp.float64):
        rng = np.random.default_rng(n)

        def mk():
            return jnp.asarray(rng.normal(size=n), dtype)

        x, gx, c, w = mk(), mk(), mk(), jnp.abs(mk())
        target = mk()
        lo = mk() - 2.0
        hi = lo + jnp.abs(mk()) + 0.1
        tau = jnp.abs(mk()) + dtype(0.05) if vector_tau else dtype(0.37)
        x1, xe = primal_update(x, gx, c, w, target, lo, hi, tau)
        rx1, rxe = primal_update_ref(x, gx, c, w, target, lo, hi, tau)
        np.testing.assert_allclose(
            np.asarray(x1), np.asarray(rx1), rtol=1e-6, atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(xe), np.asarray(rxe), rtol=1e-6, atol=1e-6
        )


@pytest.mark.parametrize("n", [5, 1024, 9000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("vector_sigma", [False, True])
def test_dual_prox_sweep(n, dtype, vector_sigma):
    with jax.enable_x64(dtype == jnp.float64):
        rng = np.random.default_rng(n + 1)

        def mk():
            return jnp.asarray(rng.normal(size=n), dtype)

        y, a = mk(), mk()
        lo = jnp.where(mk() > 0, -jnp.inf, mk())
        hi = jnp.where(mk() > 0, jnp.inf, lo + 1.0)
        sigma = jnp.abs(mk()) + dtype(0.05) if vector_sigma else dtype(0.21)
        out = dual_prox(y, a, sigma, lo, hi)
        ref = dual_prox_ref(y, a, sigma, lo, hi)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6
        )


def test_pdhg_solve_pallas_parity():
    """solver.solve with the fused Pallas update kernels (interpret mode on
    CPU) matches the pure-jnp inner iteration: same iterate path, same
    iteration count, allocations to solver tolerance."""
    from repro.core import solver
    from repro.core.nvpax import NvpaxOptions, optimize
    from repro.core.problem import AllocProblem
    from repro.pdn.tenants import assign_tenants

    pdn = build_from_level_sizes([2, 3, 2], gpus_per_server=4)
    layout = assign_tenants(pdn, n_tenants=4, devices_per_tenant=8, seed=1)
    tele = np.random.default_rng(3).uniform(100, 650, pdn.n)
    ap = AllocProblem.build(pdn, tele, sla=layout.sla_topo(), priority=layout.priority)
    ref = optimize(ap)
    pal = optimize(ap, NvpaxOptions(solver=solver.SolverOptions(use_pallas=True)))
    np.testing.assert_allclose(pal.allocation, ref.allocation, atol=1e-9)
    assert pal.stats["total_iterations"] == ref.stats["total_iterations"]


# ---------------------------------------------------------------------------
# tree_matvec
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sizes", [[2, 2], [3, 2, 2], [4, 4]])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_tree_matvec_sweep(sizes, dtype):
    with jax.enable_x64(dtype == jnp.float64):
        pdn = build_from_level_sizes(sizes, gpus_per_server=4)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=pdn.n), dtype)
        start = jnp.asarray(pdn.node_start)
        end = jnp.asarray(pdn.node_end)
        got = tree_matvec(x, start, end)
        want = tree_matvec_ref(x, start, end)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )


@pytest.mark.parametrize("sizes", [[2, 2], [3, 3]])
def test_tree_rmatvec_sweep(sizes):
    pdn = build_from_level_sizes(sizes, gpus_per_server=4)
    rng = np.random.default_rng(1)
    y = jnp.asarray(rng.normal(size=pdn.m), jnp.float32)
    start = jnp.asarray(pdn.node_start)
    end = jnp.asarray(pdn.node_end)
    got = tree_rmatvec(y, start, end, pdn.n)
    want = tree_rmatvec_ref(y, start, end, pdn.n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("block", [64, 256])
def test_tree_matvec_chunked_multi_block(block):
    """Small block/row_block force multi-block prefix grids with cross-block
    offset propagation — the path the O(100k)-device fleets exercise."""
    pdn = build_from_level_sizes([3, 2, 2], gpus_per_server=4)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=pdn.n), jnp.float32)
    start = jnp.asarray(pdn.node_start)
    end = jnp.asarray(pdn.node_end)
    got = tree_matvec(x, start, end, block=block, row_block=block)
    want = tree_matvec_ref(x, start, end)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    y = jnp.asarray(rng.normal(size=pdn.m), jnp.float32)
    got = tree_rmatvec(y, start, end, pdn.n, block=block, row_block=block)
    want = tree_rmatvec_ref(y, start, end, pdn.n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("edge_block", [16, 4096])
@pytest.mark.parametrize("n_edges", [0, 7, 300])
def test_sla_matvec_sweep(edge_block, n_edges):
    """Tenant segment sums + adjoint over random incidence edge lists,
    including the empty-tenancy fast path and multi-block edge grids."""
    n, k = 96, 5
    rng = np.random.default_rng(n_edges + edge_block)
    dev = jnp.asarray(rng.integers(0, n, n_edges), jnp.int32)
    ten = jnp.asarray(rng.integers(0, k, n_edges), jnp.int32)
    x = jnp.asarray(rng.normal(size=n), jnp.float32)
    y = jnp.asarray(rng.normal(size=k), jnp.float32)
    got = sla_matvec(x, dev, ten, k, edge_block=edge_block)
    want = sla_matvec_ref(x, dev, ten, k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    got = sla_rmatvec(y, dev, ten, n, edge_block=edge_block)
    want = sla_rmatvec_ref(y, dev, ten, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# chunk-boundary restart/KKT stats epilogues
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [9, 1000, 20000])
@pytest.mark.parametrize("block", [128, 8192])
def test_chunk_stats_match_refs(n, block):
    """The fused per-block partials reduce to the jnp oracle values (exact
    zeros from padded lanes; max/sum associativity differences stay at
    roundoff)."""
    rng = np.random.default_rng(n)

    def mk(size):
        return jnp.asarray(rng.normal(size=size), jnp.float32)

    x, px, rx, ax = mk(n), mk(n), mk(n), mk(n)
    cnt = jnp.float32(17.0)
    got = primal_chunk_stats(x, px, rx, ax, cnt, block=block)
    want = primal_chunk_stats_ref(x, px, rx, ax, cnt)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5)
    y, ry, ay = mk(n), mk(n), mk(n)
    got = dual_chunk_stats(y, ry, ay, cnt, block=block)
    want = dual_chunk_stats_ref(y, ry, ay, cnt)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# solver knobs: Pallas-native routing + blockwise omega
# ---------------------------------------------------------------------------


def _knob_problem():
    from repro.core.problem import AllocProblem
    from repro.pdn.tenants import assign_tenants

    pdn = build_from_level_sizes([2, 3, 2], gpus_per_server=4)
    layout = assign_tenants(pdn, n_tenants=4, devices_per_tenant=8, seed=1)
    tele = np.random.default_rng(3).uniform(100, 650, pdn.n)
    return AllocProblem.build(
        pdn, tele, sla=layout.sla_topo(), priority=layout.priority
    )


def test_solver_pallas_tree_and_stats_parity():
    """use_pallas_tree / use_pallas_stats route the inner matvecs and the
    chunk-boundary bookkeeping through the kernels without changing the
    solution (iterate paths agree up to reduction association)."""
    from repro.core import solver
    from repro.core.nvpax import NvpaxOptions, optimize

    ap = _knob_problem()
    ref = optimize(ap)
    for knob in ("use_pallas_tree", "use_pallas_stats"):
        opts = NvpaxOptions(solver=solver.SolverOptions(**{knob: True}))
        got = optimize(ap, opts)
        np.testing.assert_allclose(
            got.allocation, ref.allocation, atol=1e-7, err_msg=knob
        )
        assert got.stats["converged"]


def test_solver_blockwise_omega_converges_same_solution():
    """Per-dual-block primal weights change the iterate path but must land
    on the same certified allocation within solve tolerance."""
    from repro.core import solver
    from repro.core.nvpax import NvpaxOptions, optimize

    ap = _knob_problem()
    tight = dict(eps_abs=1e-9, eps_rel=1e-9)
    ref = optimize(ap, NvpaxOptions(solver=solver.SolverOptions(**tight)))
    got = optimize(
        ap, NvpaxOptions(solver=solver.SolverOptions(blockwise_omega=True, **tight))
    )
    assert got.stats["converged"]
    np.testing.assert_allclose(got.allocation, ref.allocation, atol=1e-5)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "B,Sq,Sk,H,KV,dh",
    [
        (1, 128, 128, 2, 2, 64),
        (2, 256, 256, 4, 2, 64),  # GQA
        (1, 128, 256, 2, 1, 128),  # cross-ish lengths + MQA
    ],
)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, Sq, Sk, H, KV, dh, causal):
    rng = np.random.default_rng(B * Sq + H)
    q = jnp.asarray(rng.normal(size=(B, Sq, H, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Sk, KV, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Sk, KV, dh)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, bq=64, bk=64)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_dtypes(dtype):
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), dtype)
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), dtype)
    v = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), dtype)
    out = flash_attention(q, k, v, causal=True, bq=64, bk=64)
    ref = attention_ref(q, k, v, causal=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ref, np.float32),
        rtol=tol,
        atol=tol,
    )


def test_flash_matches_model_blocked_path():
    """The model's XLA blocked attention and the Pallas kernel agree."""
    from repro.models.attention import _blocked_attention

    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 256, 2, 64)), jnp.float32)
    a = flash_attention(q, k, v, causal=True, bq=64, bk=64)
    b = _blocked_attention(q, k, v, True, 64**-0.5, 64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# memory-optimal blocked attention custom VJP (§Perf H1)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("rep", [1, 2])
def test_flash_vjp_forward_and_grads(causal, rep):
    from repro.models.flash_vjp import blocked_attention_mo

    B, S, KV, dh = 2, 128, 2, 32
    H = KV * rep
    rng = np.random.default_rng(42)
    q = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, dh)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    scale = dh**-0.5

    out = blocked_attention_mo(q, k, v, causal, scale, 32, 32)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)

    def f_mo(q, k, v):
        return jnp.vdot(blocked_attention_mo(q, k, v, causal, scale, 32, 32), ct)

    def f_ref(q, k, v):
        return jnp.vdot(attention_ref(q, k, v, causal=causal), ct)

    g_mo = jax.grad(f_mo, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_mo, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a),
            np.asarray(b),
            rtol=2e-3,
            atol=2e-3,
            err_msg=f"d{name} mismatch",
        )
