"""Set-up accounting: XLA compile time from JAX's own events, and the
split of `setup_s` into its phases."""

from __future__ import annotations

import time


class CompileClock:
    """Seconds and count of XLA backend compiles, from JAX's own events."""

    def __init__(self):
        import jax

        self.seconds, self.count = 0.0, 0
        self.names = []  # the compiled functions' names, in order

        def on_event(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.count += 1
                self.names.append(str(kw.get("fun_name", "?")))

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def mark(self):
        return self.seconds, self.count

    def since(self, mark):
        return self.seconds - mark[0], self.count - mark[1]

    def names_since(self, mark):
        return self.names[mark[1]:]


class Phases:
    """Wall seconds of named set-up phases, from a process-start stamp."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self._last = t_start
        self.split = {}

    def lap(self, name: str) -> None:
        now = time.monotonic()
        self.split[name] = self.split.get(name, 0.0) + now - self._last
        self._last = now

    def total(self) -> float:
        return self._last - self.t_start
