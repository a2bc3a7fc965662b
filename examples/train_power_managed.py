"""End-to-end driver (deliverable b): train a ~100M-parameter model for a
few hundred steps with the nvPAX power control loop in the loop.

The model is a 4-layer qwen3-family decoder (d_model 512 -> ~100M params
dominated by the 151936-token embedding).  Every control interval the
simulated job's power draw goes through the controller; the resulting caps
set the DVFS step-time multiplier that a real cluster would experience.

    PYTHONPATH=src python examples/train_power_managed.py --steps 200
"""

import argparse
import dataclasses
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.configs import get_arch
from repro.data.pipeline import SyntheticLMData
from repro.models import build
from repro.pdn.tree import build_from_level_sizes
from repro.power.controller import PowerController
from repro.power.power_model import DvfsModel, arch_power_profile
from repro.power.straggler import straggler_report
from repro.training.step import init_train_state, make_train_step


def hundred_m_config():
    base = get_arch("qwen3-4b")
    return dataclasses.replace(
        base,
        name="qwen3-100m",
        n_layers=4,
        d_model=512,
        n_heads=8,
        n_kv=4,
        d_head=64,
        d_ff=2048,
        microbatch=1,
        attn_chunk=256,
        loss_chunk=128,
        param_dtype=jnp.float32,
        compute_dtype=jnp.float32,
    )


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--control-every", type=int, default=10)
    args = ap.parse_args()

    cfg = hundred_m_config()
    api = build(cfg)
    from repro.analysis.roofline import param_counts

    print(f"model: {cfg.name}, {param_counts(cfg)['total'] / 1e6:.0f}M params")

    state, _ = init_train_state(cfg, api, jax.random.key(0))
    data = SyntheticLMData(cfg.vocab, seed=0)
    step_fn = jax.jit(
        make_train_step(cfg, api, lr=3e-3, warmup=20, total_steps=args.steps)
    )

    # this job owns 64 GPUs on a shared, oversubscribed 256-GPU PDN
    pdn = build_from_level_sizes([2, 4, 4], gpus_per_server=8)
    controller = PowerController(pdn)
    job_devices = np.arange(64)
    job_of = np.zeros(pdn.n, dtype=np.int64)
    job_of[64:] = 1 + (np.arange(pdn.n - 64) // 64)
    mean_w, burst_w, burst_p = arch_power_profile(cfg.family)
    dvfs = DvfsModel()
    rng = np.random.default_rng(0)

    losses, slowdowns = [], []
    t0 = time.time()
    for step in range(args.steps):
        batch = {
            k: jnp.asarray(v)
            for k, v in data.batch(step, args.batch, args.seq).items()
        }
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))

        if step % args.control_every == 0:
            # fleet telemetry: our job + background jobs
            draw = np.full(pdn.n, 0.0)
            draw[job_devices] = mean_w + burst_w * (
                rng.random(64) < burst_p
            )
            draw[64:] = rng.uniform(200, 680, pdn.n - 64)
            res = controller.step(draw)
            mult = dvfs.step_time_multiplier(res.allocation[job_devices])
            slowdowns.append(float(mult.max()))
            rep = straggler_report(res.allocation, job_of, dvfs)
            if step % (5 * args.control_every) == 0:
                print(
                    f"step {step:4d}  loss {losses[-1]:.3f}  "
                    f"job slowdown x{slowdowns[-1]:.3f}  "
                    f"fleet straggler tax {rep['mean_tax'] * 100:.2f}%",
                    flush=True,
                )

    print(
        f"\ntrained {args.steps} steps in {time.time() - t0:.0f}s: "
        f"loss {losses[0]:.3f} -> {losses[-1]:.3f} "
        f"(floor ~{data.bigram_entropy():.2f})\n"
        f"mean power slowdown x{np.mean(slowdowns):.3f} "
        f"(max x{np.max(slowdowns):.3f}) — nvPAX max-min fairness keeps the "
        f"synchronous job's straggler tax near zero"
    )


if __name__ == "__main__":
    main()
