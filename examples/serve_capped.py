"""Serve a small model with batched requests under datacenter power caps.

Shows the serving side of the power loop: a replica's decode throughput
under the cap nvPAX assigns to its device, across a sweep of fleet load
levels (heavier fleet -> tighter caps -> slower tokens).

    PYTHONPATH=src python examples/serve_capped.py
"""

import numpy as np

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.configs import get_arch
from repro.models import build
from repro.pdn.tree import build_from_level_sizes
from repro.power.controller import PowerController
from repro.power.power_model import DvfsModel
from repro.training.step import make_serve_steps


def main():
    use_compile_cache()
    cfg = get_arch("qwen3-4b").reduced()
    api = build(cfg)
    params, _ = api.init(jax.random.key(0))
    _, decode = make_serve_steps(cfg, api)
    decode_j = jax.jit(decode)

    B, S, G = 4, 32, 32
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, 1)), jnp.int32)
    caches = api.init_decode_cache(B, S + G)

    import time

    # measure uncapped decode
    cur = toks
    t0 = time.time()
    for i in range(G):
        logits, caches = decode_j(params, caches, cur, jnp.asarray(i, jnp.int32))
        cur = jnp.argmax(logits, -1).astype(jnp.int32)
    jax.block_until_ready(logits)
    base_tok_s = B * G / (time.time() - t0)

    # our replica is device 0 of a shared 128-GPU PDN
    pdn = build_from_level_sizes([2, 2, 4], gpus_per_server=4)
    controller = PowerController(pdn)
    dvfs = DvfsModel()
    print(f"replica uncapped: {base_tok_s:.1f} tok/s")
    print(f"{'fleet load':>12} {'our cap':>9} {'slowdown':>9} {'tok/s':>8}")
    for load in (300.0, 450.0, 550.0, 650.0):
        draw = np.full(pdn.n, load)
        draw[0] = 420.0  # decode replica draws less (memory-bound)
        res = controller.step(draw, active=np.ones(pdn.n, bool))
        cap = res.allocation[0]
        mult = float(dvfs.step_time_multiplier(np.asarray(cap)))
        print(
            f"{load:>10.0f} W {cap:>7.0f} W x{mult:>7.3f} "
            f"{base_tok_s / mult:>8.1f}"
        )


if __name__ == "__main__":
    main()
