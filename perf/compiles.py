"""Counting XLA compiles from JAX's own compile-duration events."""
from __future__ import annotations


class CompileClock:
    """Seconds XLA spent compiling, and how many compiles, since `lap()`."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.seconds = 0.0
        self.count = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_kw):
        if event == self._event:
            self.seconds += duration
            self.count += 1

    def lap(self):
        """(compiles, seconds) since the last lap."""
        out = (self.count, self.seconds)
        self.count, self.seconds = 0, 0.0
        return out
