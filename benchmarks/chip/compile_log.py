"""Lower + XLA compile seconds from JAX's own monitoring events, copied from
the repo's ``chip_smoke.py`` (``CompileLog``).  A program served from the
persistent cache still reports its (short) compile event, so the sum counts
cache loads too."""

from __future__ import annotations

import jax

# lowering to StableHLO and the XLA compile, each reported once per jitted
# program (tracing is left out: its events nest, one per inner jnp call)
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileLog:
    """Seconds and count of compile events since construction."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **kw):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            if event == _COMPILE_EVENTS[1]:
                self.compiles += 1
