"""Profiler (reference: platform/profiler.h RecordEvent tables + CUPTI
device tracer + tools/timeline.py chrome-trace export).

TPU-native design: host-side events wrap executor runs; device activity
comes from jax.profiler (XLA/TPU trace), which natively emits
chrome://tracing-compatible output — the xprof analog of the reference's
CUPTI + timeline.py pipeline. While a trace runs every RecordEvent is
also a jax.profiler.TraceAnnotation, so a device trace holds the host
spans on its own clock: ``device_profiler`` is the merged timeline.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from typing import Callable, Dict, List, Optional

import jax
import jax._src.profiler

_events: List[Dict] = []
_enabled = False
# Guards _events against concurrent RecordEvent emission (serving
# workers, prefetcher, trainer thread all append) racing a reader:
# export_chrome_trace/events/summary snapshot the list under this lock
# instead of iterating the live list, so a mid-export append can never
# tear the JSON or skip/duplicate events.
_events_lock = threading.Lock()

# Optional callback returning {"trace_id": ..., "span_id": ...} for the
# current thread — installed by observability.trace so every event
# closed under an active StepTrace span is attributable to its step.
# Kept as a late-bound hook: the profiler must not import observability.
_trace_args_provider: Optional[Callable[[], Optional[Dict]]] = None

# Always-on event listeners: called with every CLOSED RecordEvent's
# dict, even while the profiler itself is disabled. This is the feed
# for the observability layer's live attribution (step-phase breakdown)
# and the flight recorder's ring buffer — neither may depend on a user
# having started a profiling session. Listeners must be cheap and must
# not raise (exceptions are swallowed); with no listener installed the
# disabled-profiler cost stays one list truthiness test.
_event_listeners: List[Callable[[Dict], None]] = []
_listeners_lock = threading.Lock()


def add_event_listener(fn: Callable[[Dict], None]) -> None:
    """Register ``fn(event_dict)`` to observe every closed RecordEvent
    (profiler enabled or not). Idempotent and thread-safe: concurrent
    registration of the same listener installs it exactly once."""
    with _listeners_lock:
        if fn not in _event_listeners:
            _event_listeners.append(fn)


def remove_event_listener(fn: Callable[[Dict], None]) -> None:
    with _listeners_lock:
        try:
            _event_listeners.remove(fn)
        except ValueError:
            pass


def has_event_listener(fn: Callable[[Dict], None]) -> bool:
    with _listeners_lock:
        return fn in _event_listeners


def set_trace_args_provider(fn: Optional[Callable[[], Optional[Dict]]]):
    """Install a callable whose (dict) result is merged into each
    recorded event's chrome-trace ``args`` (None = no-op)."""
    global _trace_args_provider
    _trace_args_provider = fn

# Event categories ("cat" in the chrome-trace schema). Host events from
# the serving runtime (paddle_tpu.serving) are tagged so a trace of a
# live server separates queueing/batching/compile time from model time.
CAT_SERVING = "serving"
# Retry/backoff spans from paddle_tpu.resilience.retry: each retry::<op>
# event covers the backoff sleep before that retry attempt.
CAT_RESILIENCE = "resilience"
# Host/device pipelining spans (core/executor.py + trainer.py + reader
# FeedPrefetcher). The first four names partition a training step's
# SERIAL host-side time (observability.attribution maps them to the
# feed/dispatch/fetch_sync/prefetch_wait phases; anything else lands in
# the device residual):
#   pipeline::dispatch      - enqueueing the jitted step (async, cheap)
#   pipeline::fetch_sync    - materializing fetched values to host
#   pipeline::prefetch_wait - consumer waiting on the feed prefetcher
#   pipeline::host_blocked  - inline (un-prefetched) reader+feed assembly
#   pipeline::sync_barrier  - explicit device barriers (checkpoint
#                             snapshot, Executor.synchronize): device
#                             drain, deliberately NOT a feed phase
#   pipeline::prefetch_fill - producer-thread convert+upload; overlaps
#                             device compute, so never part of the
#                             serial step breakdown
# Around pipeline::dispatch, Executor.run's own host work (not mapped to
# a phase: it stays in the breakdown's device residual):
#   pipeline::prepare        - run()'s entry to the dispatch: gate and
#                              cache look-ups, feed conversion, the
#                              state arrays read from the scope (and,
#                              on a miss, the compile::* analyses)
#   pipeline::commit         - the dispatch's return to run()'s: scope
#                              repointed at the new state, StepResult
#   pipeline::globalize_feed - ParallelExecutor lifting a process-local
#                              feed onto a mesh that spans processes
CAT_PIPELINE = "pipeline"
# Per-attempt RPC spans from distributed/jsonrpc.py (rpc::<op>): one
# event per wire attempt, so retried calls show as distinct spans that
# share the originating step's trace id.
CAT_RPC = "rpc"
# The Trainer loop's own host work around each dispatch (trainer.py):
# trainer::handler around every call of the caller's event handler,
# trainer::telemetry around the per-step metrics block.
CAT_TRAINER = "trainer"
# What a first dispatch pays before it can run (core/executor.py):
# compile::verify|memory_plan|cost_model around the program's
# own analyses, and compile::jax_trace|lower|backend|cache_retrieval
# emitted closed from JAX's compile-phase events. An inner jit fires its
# own events inside an outer one's: take the UNION of a name's
# intervals, not their sum.
CAT_COMPILE = "compile"
# StepTrace root/child spans (observability/trace.py): trace::step/N
# covers one dispatched training step; every event closed inside it
# carries the step's trace_id/span_id in its args.
CAT_TRACE = "trace"


class RecordEvent:
    """RAII event (reference: profiler.h:106). `cat` is an optional
    chrome-trace category (e.g. CAT_SERVING) used to filter summaries;
    `args` lands in the chrome-trace event's args dict (merged with the
    active StepTrace context, when one is installed)."""

    def __init__(self, name: str, cat: Optional[str] = None,
                 args: Optional[Dict] = None):
        self.name = name
        self.cat = cat
        self.args = args
        self.t0 = None
        self._annotation = None

    def __enter__(self):
        # while a device trace runs (device_profiler, jax.profiler.
        # start_trace) the span is on the trace's host plane, on this
        # thread's line and the trace's clock. With no trace running no
        # annotation is made at all (_tracing)
        if _tracing():
            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        if self._annotation is not None:
            # callers close a span by hand with no arguments too
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        emit(self.name, self.t0, dur, self.cat, self.args)
        return False


# jax.profiler has no public "is a trace running"; its own session
# state is the one place that knows, however the trace was started
# (jax 0.9.0, the installation this tree is written for). Without it
# every span is annotated, which a TraceMe outside a session ignores.
_jax_profile_state = getattr(jax._src.profiler, "_profile_state", None)


def _tracing() -> bool:
    """Is a jax.profiler trace running in this process? On the chip,
    annotating every span of an UNtraced run went with rare pauses of
    1-3 s of the whole process (PERF.md, PR 25: 4 of 19 train-s256
    runs; 0 of 13 without the annotations), so spans are annotated
    only while a trace can hold them."""
    state = _jax_profile_state
    return state is None or state.profile_session is not None


def emit(name: str, start: float, dur: float, cat: Optional[str] = None,
         args: Optional[Dict] = None) -> None:
    """Record one CLOSED span (``start``/``dur`` in ``perf_counter``
    seconds) on the calling thread: what ``RecordEvent.__exit__`` does,
    and the way in for a duration that is known only once it is over
    (JAX's compile-phase events, core/executor.py). Such a span is not
    on a device trace: an annotation cannot be opened in the past."""
    listeners = _event_listeners
    if not _enabled and not listeners:
        return
    thread = threading.current_thread()
    ev = {"name": name, "ts": start * 1e6, "dur": dur * 1e6,
          "ph": "X", "pid": 0, "tid": thread.ident}
    if cat:
        ev["cat"] = cat
    args = dict(args) if args else {}
    args["thread"] = thread.name
    if _trace_args_provider is not None:
        targs = _trace_args_provider()
        if targs:
            args.update(targs)
    ev["args"] = args
    if _enabled:
        with _events_lock:
            _events.append(ev)
    # snapshot: a concurrent remove_event_listener must not skip
    # another listener mid-iteration
    for fn in list(listeners):
        try:
            fn(ev)
        except Exception:
            pass  # a broken listener must never break the hot path


def events(cat: Optional[str] = None) -> List[Dict]:
    """Snapshot of recorded host events, optionally filtered by category."""
    with _events_lock:
        snap = list(_events)
    return [e for e in snap if cat is None or e.get("cat") == cat]


def start_profiler(state: str = "All"):
    global _enabled
    _enabled = True
    with _events_lock:
        _events.clear()


def stop_profiler(sorted_key: Optional[str] = None,
                  profile_path: Optional[str] = None):
    global _enabled
    _enabled = False
    if profile_path:
        export_chrome_trace(profile_path)
    return summary()


def summary(cat: Optional[str] = None):
    agg: Dict[str, Dict] = {}
    for e in events(cat=cat):
        a = agg.setdefault(e["name"], {"calls": 0, "total_us": 0.0})
        a["calls"] += 1
        a["total_us"] += e["dur"]
    return agg


def export_chrome_trace(path: str):
    # snapshot under the lock: exporting while serving workers /
    # prefetcher threads still emit RecordEvents must serialize a
    # consistent list, not iterate one being appended to
    with _events_lock:
        snap = list(_events)
    with open(path, "w") as f:
        json.dump({"traceEvents": snap}, f)


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: Optional[str] = None,
             profile_path: Optional[str] = None):
    """Context manager parity with fluid.profiler.profiler (profiler.py:126)."""
    start_profiler(state)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def device_profiler(logdir: str):
    """TPU device trace via jax.profiler (xprof); view with tensorboard or
    Perfetto. Replaces the reference's CUPTI DeviceTracer. Every
    RecordEvent open while the trace runs is also a TraceAnnotation, so
    the trace IS the merged timeline: the program's spans sit on the
    host plane, each on its own thread's line, on the device's clock."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
