"""Problem containers for the nvPAX allocator.

Two levels:

* :class:`AllocProblem` — the *control-step* problem: fleet state (limits,
  requests, priorities, active/idle), PDN topology, tenant SLAs.  Built once
  per control step from host-side numpy (see :mod:`repro.pdn`).
* :class:`StepProblem` — one convex program in the unified QP/LP form solved
  by :mod:`repro.core.solver`:

      minimize   0.5 * sum_i w_i (x_i - target_i)^2  +  c.x  +  c_t * t
      subject to lo <= x <= hi,  t_lo <= t <= t_hi,
                 tree subtree sums        <= cap,
                 sla_lo <= tenant sums    <= sla_hi,
                 x_i - t                  >= imp_lo_i   (vacuous if -inf).

  Phase I instantiates the QP (w > 0, t pinned to 0, improvement rows
  vacuous); Phases II/III instantiate the max-min LP (w = 0, c_t = -1,
  improvement rows active on the optimized set).  All phases share one
  jitted solver because shapes are identical.
"""

from __future__ import annotations

import contextlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.treeops import SlaTopo, TreeTopo
from repro.pdn.tree import FlatPDN

__all__ = ["AllocProblem", "FleetTopology", "StepProblem", "INF"]

INF = float("inf")


class FleetTopology(NamedTuple):
    """Shape-static fleet data pre-converted to device arrays.

    Everything in :class:`AllocProblem` that does not change between control
    steps — PDN tree, tenant SLA topology, device boxes, deviation scales —
    lives here so the per-step build is only telemetry -> device arrays.
    Construct once per fleet with :meth:`from_pdn` and pass to
    ``AllocProblem.build(..., topology=...)`` (or use
    :class:`repro.core.engine.AllocEngine`, which owns one).
    """

    tree: TreeTopo
    sla: SlaTopo
    l: jnp.ndarray  # [n]
    u: jnp.ndarray  # [n]
    weight_scale: jnp.ndarray  # [n]

    @property
    def n(self) -> int:
        return self.l.shape[0]

    def with_sla_bounds(self, lo, hi, dtype=None) -> "FleetTopology":
        """Same topology with re-pinned tenant SLA row bounds.

        The SLA *structure* (incidence edges) is static engine metadata; the
        aggregate ``[lo, hi]`` rows are traced values, so swapping them
        re-pins a compiled engine without recompiling — the fleet
        coordinator's per-step tenant sub-budget path
        (:meth:`repro.core.engine.AllocEngine.set_sla_bounds`).
        """
        import jax.numpy as jnp

        dtype = dtype or self.sla.lo.dtype
        lo = jnp.asarray(lo, dtype)
        hi = jnp.asarray(hi, dtype)
        if lo.shape != self.sla.lo.shape or hi.shape != self.sla.hi.shape:
            raise ValueError(
                f"sla bounds shapes {lo.shape}/{hi.shape} != "
                f"({self.sla.k},) (structure is static; rebuild the engine)"
            )
        return self._replace(sla=self.sla._replace(lo=lo, hi=hi))

    @classmethod
    def from_pdn(
        cls,
        pdn: FlatPDN,
        *,
        sla: SlaTopo | None = None,
        normalized: bool = False,
        dtype=jnp.float64,
    ) -> "FleetTopology":
        ctx = (
            jax.enable_x64(True) if dtype == jnp.float64 else contextlib.nullcontext()
        )
        with ctx:
            if sla is None:
                sla = SlaTopo.empty(dtype)
            weight_scale = (1.0 / pdn.dev_u) if normalized else np.ones((pdn.n,))
            return cls(
                tree=TreeTopo(
                    start=jnp.asarray(pdn.node_start),
                    end=jnp.asarray(pdn.node_end),
                    cap=jnp.asarray(pdn.node_cap, dtype),
                    depth=jnp.asarray(pdn.node_depth),
                ),
                sla=SlaTopo(
                    dev=jnp.asarray(sla.dev, jnp.int32),
                    ten=jnp.asarray(sla.ten, jnp.int32),
                    lo=jnp.asarray(sla.lo, dtype),
                    hi=jnp.asarray(sla.hi, dtype),
                ),
                l=jnp.asarray(pdn.dev_l, dtype),
                u=jnp.asarray(pdn.dev_u, dtype),
                weight_scale=jnp.asarray(weight_scale, dtype),
            )


class AllocProblem(NamedTuple):
    """One control step's allocation problem (jnp arrays)."""

    # fleet
    l: jnp.ndarray  # [n] device minimum power
    u: jnp.ndarray  # [n] device maximum power
    r: jnp.ndarray  # [n] requests, clipped to [l, u]; r = l for idle
    priority: jnp.ndarray  # [n] int32 in {1..P}, higher = more important
    active: jnp.ndarray  # [n] bool
    # constraints
    tree: TreeTopo
    sla: SlaTopo
    # options
    weight_scale: jnp.ndarray  # [n] per-device deviation scale (1 or 1/u_i)

    @property
    def n(self) -> int:
        return self.l.shape[0]

    @property
    def idle(self) -> jnp.ndarray:
        return ~self.active

    # -- precomputed level metadata (host-side; requires concrete arrays) --
    #
    # These drive the fixed-trip jax control flow of the three-phase driver
    # in :mod:`repro.core.batched`: the priority sweep scans over
    # ``priority_levels()`` and the feasibility repair runs
    # ``n_tree_depths()`` fori-loop trips.

    def priority_levels(self, active_only: bool = True) -> tuple[int, ...]:
        """Distinct priority values, descending (Algorithm 1 sweep order).

        ``active_only`` restricts to levels present among active devices —
        what :func:`repro.core.batched.batch_meta` compiles.  Must be called on concrete (untraced)
        arrays; the result is static metadata for jitted programs.
        """
        pri = np.asarray(self.priority)
        if active_only:
            pri = pri[np.asarray(self.active)]
        return tuple(sorted({int(p) for p in pri}, reverse=True))

    def n_tree_depths(self) -> int:
        """Number of distinct PDN tree levels (root depth 0 included)."""
        depth = np.asarray(self.tree.depth)
        return int(depth.max()) + 1 if depth.size else 0

    def pin_free_ok(self) -> bool:
        """True when free devices can be pinned at ``l`` in Phase I: no
        tenant lower-bound SLA could force an idle device upward (paper
        section 4.3.1)."""
        return self.sla.k == 0 or not bool((np.asarray(self.sla.lo) > 0).any())

    @classmethod
    def build(
        cls,
        pdn: FlatPDN,
        requests: np.ndarray,
        *,
        active: np.ndarray | None = None,
        priority: np.ndarray | None = None,
        idle_threshold: float = 150.0,
        sla: SlaTopo | None = None,
        normalized: bool = False,
        dtype=jnp.float64,
        topology: FleetTopology | None = None,
    ) -> "AllocProblem":
        """Assemble a control-step problem from a flattened PDN + telemetry.

        Mirrors the paper's request pre-processing (section 5.2): requests
        are clipped to ``[l, u]``; a device is idle if its raw request is
        below ``idle_threshold`` (unless an explicit ``active`` mask, e.g.
        from the job scheduler, is given); idle devices request ``l``.

        ``topology`` is the zero-rebuild fast path: a prebuilt
        :class:`FleetTopology` whose device arrays are reused as-is, so the
        per-step host work is only the O(n) request pre-processing plus the
        telemetry transfer (``sla``/``normalized`` are then taken from the
        topology and must not be passed).
        """
        n = pdn.n
        requests = np.asarray(requests, dtype=np.float64)
        if requests.shape != (n,):
            raise ValueError(f"requests shape {requests.shape} != ({n},)")
        if active is None:
            active = requests >= idle_threshold
        active = np.asarray(active, dtype=bool)
        r = np.clip(requests, pdn.dev_l, pdn.dev_u)
        r = np.where(active, r, pdn.dev_l)
        if priority is None:
            priority = np.ones((n,), dtype=np.int32)
        priority = np.asarray(priority, dtype=np.int32)
        if (priority < 1).any():
            raise ValueError("priorities must be >= 1")
        # f64 conversion must happen under an x64 context or jax silently
        # truncates to f32.
        ctx = (
            jax.enable_x64(True) if dtype == jnp.float64 else contextlib.nullcontext()
        )
        with ctx:
            if topology is None:
                topology = FleetTopology.from_pdn(
                    pdn, sla=sla, normalized=normalized, dtype=dtype
                )
            elif sla is not None or normalized:
                raise ValueError(
                    "sla/normalized are fixed by the prebuilt topology"
                )
            return cls(
                l=topology.l,
                u=topology.u,
                r=jnp.asarray(r, dtype),
                priority=jnp.asarray(priority),
                active=jnp.asarray(active),
                tree=topology.tree,
                sla=topology.sla,
                weight_scale=topology.weight_scale,
            )


class StepProblem(NamedTuple):
    """One convex program in the unified form (see module docstring)."""

    # objective
    w: jnp.ndarray  # [n] diagonal quadratic weights (0 for LP)
    target: jnp.ndarray  # [n] quadratic targets
    c: jnp.ndarray  # [n] linear cost on x
    c_t: jnp.ndarray  # scalar linear cost on t
    # variable boxes
    lo: jnp.ndarray  # [n]
    hi: jnp.ndarray  # [n]
    t_lo: jnp.ndarray  # scalar
    t_hi: jnp.ndarray  # scalar
    # row bounds (tree lower bound is implicitly -inf)
    tree_hi: jnp.ndarray  # [m]
    sla_lo: jnp.ndarray  # [k]
    sla_hi: jnp.ndarray  # [k]
    imp_lo: jnp.ndarray  # [n]; -inf disables row i

    @property
    def n(self) -> int:
        return self.w.shape[0]
