"""Kernel microbench: interpret-mode correctness + XLA-path timings of the
operations the Pallas kernels replace (CPU container: wall times are for
the pure-jnp path the kernels are validated against; the VMEM-tiled kernels
target TPU and cannot be timed here — their win is structural: one fused
HBM pass vs ~15 elementwise round trips, see EXPERIMENTS.md §Perf)."""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.pdhg_update import primal_update
from repro.kernels.pdhg_update.ref import primal_update_ref
from repro.kernels.tree_matvec import tree_matvec
from repro.kernels.tree_matvec.ref import tree_matvec_ref
from repro.pdn.tree import build_from_level_sizes


def _time(f, *args, reps=5):
    f(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # us


def run() -> dict:
    out = {}
    # pdhg_update correctness + ref timing at fleet scale
    n = 100_000
    rng = np.random.default_rng(0)

    def mk():
        return jnp.asarray(rng.normal(size=n), jnp.float32)

    x, gx, c, w, tg = mk(), mk(), mk(), jnp.abs(mk()), mk()
    lo, hi = mk() - 3, mk() + 3
    tau = jnp.float32(0.3)
    k_out = primal_update(x, gx, c, w, tg, lo, hi, tau)
    r_out = primal_update_ref(x, gx, c, w, tg, lo, hi, tau)
    out["pdhg_update_allclose"] = bool(
        np.allclose(np.asarray(k_out[0]), np.asarray(r_out[0]), atol=1e-5)
    )
    ref_jit = jax.jit(primal_update_ref)
    out["pdhg_update_ref_us"] = _time(ref_jit, x, gx, c, w, tg, lo, hi, tau)

    # tree_matvec
    pdn = build_from_level_sizes([4, 8, 8], gpus_per_server=8)
    xs = jnp.asarray(rng.normal(size=pdn.n), jnp.float32)
    st, en = jnp.asarray(pdn.node_start), jnp.asarray(pdn.node_end)
    out["tree_matvec_allclose"] = bool(
        np.allclose(
            np.asarray(tree_matvec(xs, st, en)),
            np.asarray(tree_matvec_ref(xs, st, en)),
            atol=1e-3,
        )
    )
    ref2 = jax.jit(tree_matvec_ref)
    out["tree_matvec_ref_us"] = _time(ref2, xs, st, en)

    # flash attention (small shape on CPU interpret)
    q = jnp.asarray(rng.normal(size=(1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    fa = flash_attention(q, k, v, causal=True, bq=64, bk=64)
    ra = attention_ref(q, k, v, causal=True)
    out["flash_attention_allclose"] = bool(
        np.allclose(np.asarray(fa), np.asarray(ra), atol=3e-3)
    )
    ref3 = jax.jit(lambda q, k, v: attention_ref(q, k, v, causal=True))
    out["attention_ref_us"] = _time(ref3, q, k, v)
    return out


if __name__ == "__main__":
    use_compile_cache()
    import json

    print(json.dumps(run(), indent=1))
