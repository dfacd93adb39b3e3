"""What the benchmark hears from the program while it runs: every
closed ``profiler.RecordEvent`` (through the always-on listener hook)
and JAX's backend-compile durations. All times are host
``time.perf_counter()`` seconds; ``heard`` is the benchmark's own
clock reading when the listener fired, i.e. the span's end."""
from __future__ import annotations

import time


class Span:
    __slots__ = ("name", "start", "dur", "heard")

    def __init__(self, name, start, dur, heard):
        self.name, self.start, self.dur, self.heard = name, start, dur, heard

    @property
    def end(self):
        return self.start + self.dur


class Collector:
    """One per process. ``install()`` hooks the program; ``spans`` and
    ``compiles`` grow as it runs (list.append is atomic, and readers
    only look after the threads that write have been joined or between
    steps)."""

    def __init__(self):
        self.spans: list = []
        self.compiles: list = []          # (heard, seconds)
        self._installed = False

    def _on_event(self, ev):
        heard = time.perf_counter()
        self.spans.append(Span(ev["name"], ev["ts"] * 1e-6,
                               ev["dur"] * 1e-6, heard))

    def _on_duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.perf_counter(), float(secs)))

    def install(self):
        if self._installed:
            return self
        import jax.monitoring as mon
        from paddle_tpu import profiler
        profiler.add_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)
        self._installed = True
        return self

    def uninstall(self):
        """Detach from the program's profiler. JAX's monitoring has no
        public way to drop one listener; ours stays, appending to a
        list nobody reads, for the rest of the process."""
        from paddle_tpu import profiler
        profiler.remove_event_listener(self._on_event)
        self._installed = False

    # -- reading -----------------------------------------------------
    def named(self, prefix: str, t0=None, t1=None) -> list:
        """Spans whose name starts with ``prefix`` and that ENDED inside
        [t0, t1] (either bound may be None)."""
        return [s for s in list(self.spans)
                if s.name.startswith(prefix)
                and (t0 is None or s.end >= t0)
                and (t1 is None or s.end <= t1)]

    def _compiles(self, t0, t1) -> list:
        return [s for t, s in list(self.compiles)
                if (t0 is None or t >= t0) and (t1 is None or t <= t1)]

    def compile_seconds(self, t0=None, t1=None) -> float:
        return sum(self._compiles(t0, t1))

    def compile_count(self, t0=None, t1=None) -> int:
        return len(self._compiles(t0, t1))
