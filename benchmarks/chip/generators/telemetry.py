"""The general telemetry generator: synthetic per-device power, driven by a
mix file of parameters (``mixes/<name>.json`` with ``"generator":
"telemetry"``).

Copied from ``src/repro/pdn/telemetry.py`` (``TelemetrySim``) so that the
yardstick cannot move with the program.  The demand model is the same:
jobs of geometric size drawn from a busy/moderate mixture, a fleet-wide
diurnal envelope, per-job bursts, a fixed idle share re-drawn at every
churn epoch, and per-device jitter.

One change makes every run seed carry the same work.  The source draws
the jobs and their placement from its one seed, so the work moved with
it: against the hall's 5.28 MW root cap some seeds sit in shortage with
several times the solver iterations of the others.  Here the jobs (sizes,
placement, band positions, busy/idle per epoch, diurnal phase) come from
a placement seed fixed in the mix; the run seed draws the per-interval
noise (jitter and its per-device scale, idle draws, bursts).  So every
seed offers the same jobs on the same devices, with other noise.

The ring a run replays (``replay``) is one segment per placement seed in
``trace_seeds``: ``segment`` intervals of consecutive trace time each, the
segments following each other in time from ``start``.  Within a segment
the telemetry holds each trace step for ``hold`` intervals (1: a new step
every interval; more: a quasi-static load).
"""

from __future__ import annotations

import numpy as np

DAY_STEPS = 2880  # 24 h at a 30 s cadence


class Telemetry:
    """Per-device power at trace step ``t`` for one job placement; a pure
    function of (mix, n, placement seed, run seed, t)."""

    def __init__(self, mix: dict, n_devices: int, trace_seed: int, seed: int):
        self.mix = mix
        self.n = int(n_devices)
        self.trace_seed = int(trace_seed)
        self.seed = int(seed) & 0xFFFFFFFFFFFFFFFF
        jobs = np.random.default_rng(self.trace_seed)
        sizes = []
        left = self.n
        while left > 0:
            s = int(jobs.geometric(1.0 / mix["mean_job_size"]))
            s = max(1, min(s, left))
            sizes.append(s)
            left -= s
        self.n_jobs = len(sizes)
        self.job_phase = jobs.uniform(0, 2 * np.pi)  # fleet-wide diurnal phase
        self.job_u = jobs.random(self.n_jobs)  # each job's place in its band
        order = np.random.default_rng([self.trace_seed, 0]).permutation(self.n_jobs)
        self.job_of = np.repeat(order, np.asarray(sizes)[order])  # placement
        scale = np.random.default_rng([self.seed, 0])
        self.dev_jitter = scale.uniform(0.5, 1.5, self.n)

    def _epoch(self, t: int) -> tuple[np.ndarray, np.ndarray]:
        """(job running, job busy) for the churn epoch holding step ``t``:
        exactly ``round(idle_fraction * jobs)`` jobs idle, and each running
        job near TDP with probability ``busy_fraction``."""
        mix = self.mix
        rng = np.random.default_rng([self.trace_seed, 1, t // mix["epoch_len"]])
        perm = rng.permutation(self.n_jobs)
        running = np.ones(self.n_jobs, bool)
        running[perm[: int(round(mix["idle_fraction"] * self.n_jobs))]] = False
        busy = rng.random(self.n_jobs) < mix["busy_fraction"]
        return running, busy

    def power(self, t: int) -> np.ndarray:
        """Measured per-device power (watts) at trace step ``t``."""
        mix = self.mix
        rng = np.random.default_rng([self.seed, 2, self.trace_seed, t])
        diurnal = 1.0 + mix["diurnal_amplitude"] * np.sin(
            2 * np.pi * t / DAY_STEPS + self.job_phase
        )
        running, busy = self._epoch(t)
        burst = np.where(
            rng.random(self.n_jobs) < mix["burst_prob"], mix["burst_gain"], 1.0
        )
        lo_b, hi_b = mix["busy_band"]
        lo_m, hi_m = mix["moderate_band"]
        band = np.where(
            busy, lo_b + self.job_u * (hi_b - lo_b), lo_m + self.job_u * (hi_m - lo_m)
        )
        job_power = band * diurnal * burst
        jitter = rng.normal(0.0, mix["jitter_w"], self.n) * self.dev_jitter
        idle = rng.uniform(*mix["idle_band"], self.n)
        return np.where(running[self.job_of], job_power[self.job_of] + jitter, idle)


def replay(mix: dict, n_devices: int, seed: int) -> np.ndarray:
    """``[len(trace_seeds) * segment, n]`` telemetry, which the control loop
    replays in a ring."""
    seg, hold = int(mix["segment"]), int(mix.get("hold", 1))
    out = []
    for k, ts in enumerate(mix["trace_seeds"]):
        sim = Telemetry(mix, n_devices, ts, seed)
        t0 = int(mix["start"]) + k * seg
        out += [sim.power(t0 + (i // hold) * hold) for i in range(seg)]
    return np.stack(out)
