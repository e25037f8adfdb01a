"""What every driver shares: the compile clock and the record of one
measured window. The drivers themselves are modules of ``bench/drivers``.
"""
from __future__ import annotations

#: JAX's compile-time events: tracing, lowering, backend compile (the
#: last also covers a persistent-cache read).
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Sums the compile-time events JAX reports, from any thread."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event in COMPILE_EVENTS:
            self.seconds += secs
            if event == COMPILE_EVENTS[-1]:
                self.compiles += 1


class Window:
    """What one window measured."""

    def __init__(self):
        self.t0 = self.t1 = 0.0
        self.attempted = self.failed = 0
        self.designs = 0
        self.compiles = 0
        self.answers: list[dict] = []

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0
