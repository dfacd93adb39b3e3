"""The calling thread's own clock beside the wall clock: what tells a
thread that RUNS from one that waits — for the interpreter's lock, for
another lock, for a core — which no span can (a span's two ends are wall
readings).

``read()`` is five numbers:

    wall          ``time.perf_counter()``, the clock of profiler spans
    cpu           ``time.thread_time()``: this thread's user + system CPU
                  seconds (CLOCK_THREAD_CPUTIME_ID, nanoseconds)
    voluntary     context switches this thread asked for (it blocked)
    involuntary   context switches it did not ask for (pre-empted)
    process_cpu   ``time.process_time()``: every thread's CPU seconds

The two switch counts come from ``resource.getrusage(RUSAGE_THREAD)``
and are None where the platform has no ``RUSAGE_THREAD``. The same
call's ``ru_utime + ru_stime`` is NOT the cpu reading: on Linux it moves
in scheduler ticks (4 ms where PR 55 read it), a decode pass is 5-30 ms.

A reading is three system calls: under 2 us on a plain kernel, 18 us in
a tight loop on a sandboxed one that answers them in its own process
(gVisor, the chip's host: PR 55) and several times that between other
work, where each also moves in 10-ms ticks and the switch counts stay
0. So a hot loop reads its clock a stretch of its passes at a time
(serving/generation/engine.py _account), not a pass at a time.

Differences of two readings on ONE thread are what mean something.
Over an interval ``cpu`` cannot pass ``wall`` — up to the rate at which
the kernel's two clocks differ (the monotonic clock is slewed to keep
time, the scheduler's is not): a thread that ran all of 100 ms has read
10 us more CPU than wall on the sandbox.
"""
from __future__ import annotations

import time
from typing import NamedTuple, Optional

try:
    import resource
    _RUSAGE_THREAD = resource.RUSAGE_THREAD
except (ImportError, AttributeError):     # not Linux
    resource = None
    _RUSAGE_THREAD = None

__all__ = ["Reading", "read"]


class Reading(NamedTuple):
    wall: float
    cpu: float
    voluntary: Optional[int]
    involuntary: Optional[int]
    process_cpu: float


def read() -> Reading:
    voluntary = involuntary = None
    if _RUSAGE_THREAD is not None:
        ru = resource.getrusage(_RUSAGE_THREAD)
        voluntary, involuntary = ru.ru_nvcsw, ru.ru_nivcsw
    return Reading(time.perf_counter(), time.thread_time(), voluntary,
                   involuntary, time.process_time())
