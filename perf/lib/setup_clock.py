"""Itemised set-up time and the count of compilations inside the window."""

from __future__ import annotations

import time


class SetupClock:
    """`mark(name)` closes the phase that began at the last mark; phases are
    seconds since the process's first instant, itemised for PERF.md."""

    def __init__(self, t_start: float) -> None:
        self.t_start = t_start
        self._last = t_start
        self.items = []

    def mark(self, name: str) -> None:
        now = time.monotonic()
        self.items.append([name, now - self._last])
        self._last = now

    def total(self) -> float:
        return self._last - self.t_start


class CompileWatch:
    """Counts the executables JAX builds (XLA compilation, or a load from
    the persistent cache in its place) and remembers when, so that a window
    can say how many fell inside it."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        from jax import monitoring

        self.events = []  # (monotonic instant it ended, seconds it took)
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.events.append((time.monotonic(), secs))

    def between(self, t0: float, t1: float) -> list:
        """[seconds after t0 it ended, seconds it took] of each one that
        ended inside [t0, t1]: a run's series keeps them, so that a stall
        in the window can be told from a compilation."""
        return [[t - t0, secs] for t, secs in self.events if t0 <= t <= t1]

    def count_between(self, t0: float, t1: float) -> int:
        return len(self.between(t0, t1))
