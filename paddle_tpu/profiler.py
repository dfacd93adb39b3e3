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

import bisect
import collections
import contextlib
import gc
import glob
import json
import os
import re
import statistics
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

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
# What the interpreter does to every thread at once (this file):
# runtime::gc around each FULL garbage collection, on the thread that
# set it off, handed to the listeners with the next span that closes.
# One is 79-323 ms on the chip's host (PERF.md section 7) and stops the
# dispatch loop with everything else.
CAT_RUNTIME = "runtime"
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
    if not _enabled and not _event_listeners:
        return
    if _gc_pending:
        _emit_collections()
    thread = threading.current_thread()
    _record(name, start, dur, cat, args, thread.ident, thread.name)


def _record(name, start, dur, cat, args, ident, thread_name) -> None:
    listeners = _event_listeners
    ev = {"name": name, "ts": start * 1e6, "dur": dur * 1e6,
          "ph": "X", "pid": 0, "tid": ident}
    if cat:
        ev["cat"] = cat
    args = dict(args) if args else {}
    args["thread"] = thread_name
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


# Full collections closed and not yet handed to the listeners:
# (start, seconds, thread ident, objects collected)
_gc_pending: "collections.deque" = collections.deque(maxlen=64)
_gc_open = None


def _on_gc(phase: str, info: Dict) -> None:
    """The ``gc.callbacks`` hook: a span around a collection of the
    oldest generation. The younger ones, thousands a second, return at
    once: no span, no clock read. A collection starts inside whatever
    allocation set it off, which may be inside a listener's own
    critical section (attribution's and the flight recorder's locks are
    not reentrant): so the hook runs no listener and takes no lock. It
    reads the clock, holds a TraceAnnotation open while a device trace
    runs (the trace's own span, written as it happens), and leaves the
    closed span for the next `emit` to hand on. Collections do not nest
    and ``start`` and ``stop`` come on one thread: one slot."""
    global _gc_open
    if info["generation"] < 2:
        return
    if phase == "start":
        annotation = None
        if _tracing():
            annotation = jax.profiler.TraceAnnotation("runtime::gc")
            annotation.__enter__()
        _gc_open = (time.perf_counter(), annotation)
    elif _gc_open is not None:
        (start, annotation), _gc_open = _gc_open, None
        seconds = time.perf_counter() - start
        if annotation is not None:
            annotation.__exit__(None, None, None)
        if _enabled or _event_listeners:
            _gc_pending.append((start, seconds, threading.get_ident(),
                                info.get("collected")))


gc.callbacks.append(_on_gc)


def _emit_collections() -> None:
    """Hand the collections `_on_gc` left to the recorder and the
    listeners, each under the thread that collected."""
    threads = {t.ident: t.name for t in threading.enumerate()}
    while _gc_pending:
        try:
            start, seconds, ident, collected = _gc_pending.popleft()
        except IndexError:      # another thread emptied it
            return
        _record("runtime::gc", start, seconds, CAT_RUNTIME,
                {"collected": collected}, ident,
                threads.get(ident, str(ident)))


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
    return summary(sorted_key=sorted_key)


# sorted_key of the reference's profiler (profiler.py stop_profiler):
# which column orders the report, largest first
_SORT_COLUMNS = {"calls": "calls", "total": "total_us", "max": "max_us",
                 "min": "min_us", "ave": "ave_us"}


def summary(cat: Optional[str] = None,
            sorted_key: Optional[str] = None):
    """{span name: calls, total_us, max_us, min_us, ave_us} of the host
    spans recorded; in the order recorded, or largest first by
    ``sorted_key`` (calls / total / max / min / ave). The device's time
    by program op is `device_op_times`."""
    if sorted_key is not None and sorted_key not in _SORT_COLUMNS:
        raise ValueError(f"sorted_key {sorted_key!r} is none of "
                         f"{sorted(_SORT_COLUMNS)}")
    agg: Dict[str, Dict] = {}
    for e in events(cat=cat):
        a = agg.setdefault(e["name"], {
            "calls": 0, "total_us": 0.0, "max_us": 0.0,
            "min_us": float("inf")})
        a["calls"] += 1
        a["total_us"] += e["dur"]
        a["max_us"] = max(a["max_us"], e["dur"])
        a["min_us"] = min(a["min_us"], e["dur"])
    for a in agg.values():
        a["ave_us"] = a["total_us"] / a["calls"]
    if sorted_key is not None:
        column = _SORT_COLUMNS[sorted_key]
        agg = dict(sorted(agg.items(), key=lambda kv: -kv[1][column]))
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


# -- device time by program op -------------------------------------------
#
# The plain form of a device trace that the reduction works on (the
# benchmark's chipbench/trace.py reads the same file into the same
# form; the program may not import the benchmark):
#
#   {"planes": [{"name": "/device:TPU:0",
#                "lines": [{"name": "XLA Ops",
#                           "events": [[name, start_ns, dur_ns], ...]},
#                          {"name": "XLA Modules", "events": [...]}]}]}

_DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:(\d+)")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


def load_device_trace(logdir: str) -> Dict:
    """The newest ``.xplane.pb`` under the directory `device_profiler`
    wrote, in the plain form above: the operations and modules lines of
    every device plane, read with JAX alone."""
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        lines = [{"name": line.name, "events": [
            [e.name, float(e.start_ns), float(e.duration_ns)]
            for e in line.events]}
            for line in plane.lines
            if line.name in (_OPS_LINE, _MODULES_LINE)]
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def instruction_name(event_name: str) -> str:
    """``fusion.717`` of an operation event's name, which is the whole
    HLO line (``%fusion.717 = bf16[...] fusion(...)``) in a trace and
    ``fusion.717 fusion`` in the benchmark's plain form."""
    head = event_name.partition(" = ")[0] if " = " in event_name \
        else event_name.split(" ", 1)[0]
    return head.lstrip("%")


def self_times(events) -> List:
    """[name, own ns] of every event: its duration less that of the
    events inside it (a ``while`` is an event around its body's), so
    that the sum over events is the time the device was busy."""
    out, stack = [], []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(end, stack[-1][0]) - start
        stack.append((end, len(out)))
        out.append([name, dur])
    return out


def _runs(line_events, ops) -> List[List]:
    """The operation events grouped by the run of a module they started
    in: [[module name or None, [event, ...]], ...]."""
    runs = sorted(line_events, key=lambda e: e[1])
    if not runs:
        return [[None, ops]]
    starts = [r[1] for r in runs]
    groups = [[r[0].split("(", 1)[0], []] for r in runs]
    outside = [None, []]
    for ev in ops:
        i = bisect.bisect_right(starts, ev[1]) - 1
        inside = i >= 0 and ev[1] < runs[i][1] + runs[i][2]
        (groups[i] if inside else outside)[1].append(ev)
    return [g for g in groups + [outside] if g[1]]


def op_times(trace: Dict, programs: Sequence) -> Dict[int, Dict]:
    """A device trace in the plain form, reduced through the op tables
    of `programs` (cache entries: ``op_table()``, ``cost``,
    ``program_ops()``). Per device id::

        {"busy_s": ..., "unmapped_s": ..., "unmapped_top": [[name, s]],
         "mixed_fusion_s": ...,
         "programs": [{"program": i, "module", "uid", "runs", "seconds",
                       "static_by_type": {role: {type: [flops, bytes]}}}],
         "rows": [{"program": i, "role", "op_type", "block_path",
                   "op_index", "seconds", "calls",
                   "flops", "bytes_accessed"}, ...]}   # heaviest first

    ``seconds`` are an instruction's own (`self_times`); ``flops`` and
    ``bytes_accessed`` are the cost model's static counts of the same op
    for ONE run of the program and its ``runs`` how often it ran, so a
    row's achieved rate is ``flops * runs / seconds``. Rows and
    ``unmapped_s`` sum to ``busy_s``.

    **Which program an event belongs to.** Module names collide (every
    step program is ``jit_step_fn``) and so do instruction names
    (``fusion.1``). The events of one run of a module (the "XLA Modules"
    line, where the trace has one; else the whole line is one run) go to
    the programs whose table holds EVERY instruction name seen in that
    run; where none does (a window without the modules line that ran
    several programs) each event goes to the programs that hold its
    name. If those programs agree on the event's op the time goes to
    that op's row under the first of them; if they disagree it goes to
    the one row whose role is ``ambiguous``, never to a guess. An
    instruction that a table holds with no program op in its metadata,
    or that no table holds, is ``unmapped``."""
    tables = [p.op_table() for p in programs]
    names = [frozenset(t.ops) | frozenset(t.unmapped) for t in tables]
    out = {}
    for plane in trace["planes"]:
        m = _DEVICE_PLANE.match(plane["name"])
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        if not m or not lines.get(_OPS_LINE):
            continue
        ops = lines[_OPS_LINE]
        rows, unmapped, counts = {}, {}, [{} for _ in tables]
        mixed_ns = 0.0
        for module, events in _runs(lines.get(_MODULES_LINE) or [], ops):
            seen = {instruction_name(e[0]) for e in events}
            fits = [i for i, t in enumerate(tables)
                    if module in (None, t.module)]
            whole = [i for i in fits if seen <= names[i]]
            for name, ns in self_times(events):
                inst = instruction_name(name)
                holders = [i for i in (whole or fits) if inst in names[i]]
                refs = {tables[i].ops.get(inst) for i in holders}
                if len(refs) > 1:
                    key = (None, "ambiguous", "", (), -1)
                elif not holders or None in refs:
                    unmapped[inst] = unmapped.get(inst, 0.0) + ns
                    continue
                else:
                    i, (ref,) = holders[0], refs
                    key = (i, ref.role, ref.op_type, ref.block_path,
                           ref.op_index)
                    counts[i][inst] = counts[i].get(inst, 0) + 1
                    if inst in tables[i].mixed:
                        mixed_ns += ns
                row = rows.setdefault(key, [0.0, 0])
                row[0] += ns
                row[1] += 1
        out[int(m.group(1))] = _op_time_report(
            programs, tables, rows, unmapped, counts, mixed_ns,
            _union_ns(ops))
    return out


def _union_ns(events) -> float:
    busy, reach = 0.0, float("-inf")
    for _n, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if end > reach:
            busy += end - max(start, reach)
            reach = end
    return busy


def _op_time_report(programs, tables, rows, unmapped, counts, mixed_ns,
                    busy_ns) -> Dict:
    listed, costs = {}, {}
    for i, seen in enumerate(counts):
        if not seen:
            continue
        kinds = programs[i].program_ops()
        costs[i] = {(c.block_path[-1], c.op_index): c
                    for c in getattr(programs[i].cost, "ops", ())}
        static = {}
        for where, c in costs[i].items():
            if where in kinds:
                role, op_type = kinds[where]
                both = static.setdefault(role, {}).setdefault(
                    op_type, [0, 0])
                both[0] += c.flops
                both[1] += c.bytes_accessed
        listed[i] = {"program": i, "module": tables[i].module,
                     "uid": getattr(programs[i], "uid", None),
                     # an instruction outside a loop runs once a run
                     "runs": statistics.mode(seen.values()),
                     "seconds": 0.0, "static_by_type": static}
    out_rows = []
    for (i, role, op_type, path, index), (ns, calls) in rows.items():
        c = costs[i].get((path[-1], index)) if path else None
        out_rows.append({
            "program": i, "role": role, "op_type": op_type,
            "block_path": path, "op_index": index,
            "seconds": ns * 1e-9, "calls": calls,
            "flops": c.flops if c else None,
            "bytes_accessed": c.bytes_accessed if c else None})
        if i is not None:
            listed[i]["seconds"] += ns * 1e-9
    out_rows.sort(key=lambda r: -r["seconds"])
    top = sorted(unmapped.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": busy_ns * 1e-9,
            "unmapped_s": sum(unmapped.values()) * 1e-9,
            "unmapped_top": [[n, ns * 1e-9] for n, ns in top],
            "mixed_fusion_s": mixed_ns * 1e-9,
            "programs": list(listed.values()), "rows": out_rows}


def device_op_times(logdir: str,
                    programs: Optional[Sequence] = None) -> Dict[int, Dict]:
    """Device time by program op: the trace under `logdir` (what
    `device_profiler` wrote) reduced by `op_times` through the tables of
    `programs`, by default every step program compiled in this process
    (`core.executor.compiled_programs`; each builds its table here, on
    the first ask)."""
    if programs is None:
        from .core.executor import compiled_programs
        programs = compiled_programs()
    return op_times(load_device_trace(logdir), programs)


def op_time_table(times: Dict, by: str = "type",
                  sorted_key: str = "total", limit: int = 20,
                  uid: Optional[int] = None) -> str:
    """One device's `op_times` as text, heaviest first: a line a program
    op (``by="op"``) or an op type (``by="type"``) with its milliseconds
    a run of the program, its share of busy time, the cost model's
    static GFLOP and MB a run and the rates they make over the measured
    time. ``sorted_key``: total / calls / ave (seconds an instruction
    executed); ``uid`` keeps one Program's rows. What the reference's
    ``stop_profiler(sorted_key=...)`` printed per operator, for a device
    that fuses across operators."""
    listed = {p["program"]: p for p in times["programs"]}
    agg = {}
    for r in times["rows"]:
        if uid is not None and \
                listed.get(r["program"], {}).get("uid") != uid:
            continue
        key = (r["program"], r["role"], r["op_type"]) + (
            (r["block_path"], r["op_index"]) if by == "op" else ())
        a = agg.setdefault(key, {"seconds": 0.0, "calls": 0,
                                 "flops": None, "bytes": None})
        a["seconds"] += r["seconds"]
        a["calls"] += r["calls"]
        if by == "op":
            a["flops"], a["bytes"] = r["flops"], r["bytes_accessed"]
        else:
            a["flops"], a["bytes"] = listed.get(r["program"], {}).get(
                "static_by_type", {}).get(r["role"], {}).get(
                r["op_type"], (None, None))
    order = {"total": lambda a: -a["seconds"],
             "calls": lambda a: -a["calls"],
             "ave": lambda a: -a["seconds"] / a["calls"]}[sorted_key]
    busy = times["busy_s"] or float("nan")
    lines = [f"device busy {times['busy_s']:.4f} s; no program op "
             f"{times['unmapped_s'] / busy * 100:.1f} %; in fusions over "
             f"several ops {times['mixed_fusion_s'] / busy * 100:.1f} %",
             f"{'ms/run':>9s} {'share%':>7s} {'calls':>7s} {'GFLOP':>9s} "
             f"{'MB':>9s} {'TFLOP/s':>8s} {'GB/s':>7s}  role       op"]

    def num(value, scale):
        return "-" if value is None else f"{value / scale:.1f}"

    ranked = sorted(agg.items(), key=lambda kv: order(kv[1]))
    for key, a in ranked[:limit]:
        runs = listed.get(key[0], {}).get("runs") or 1
        per_run = a["seconds"] / runs
        tflops = a["flops"] / per_run if a["flops"] and per_run else None
        gbs = a["bytes"] / per_run if a["bytes"] and per_run else None
        where = "" if by != "op" or not key[3] else \
            " b" + "/".join(map(str, key[3])) + f":op{key[4]}"
        lines.append(
            f"{per_run * 1e3:9.3f} {a['seconds'] / busy * 100:7.2f} "
            f"{a['calls']:7d} {num(a['flops'], 1e9):>9s} "
            f"{num(a['bytes'], 1e6):>9s} {num(tflops, 1e12):>8s} "
            f"{num(gbs, 1e9):>7s}  {key[1]:10s} {key[2]}{where}")
    if len(ranked) > limit:
        lines.append(f"  ... {len(ranked) - limit} more")
    for name, secs in times["unmapped_top"][:5]:
        lines.append(f"{'':9s} {secs / busy * 100:7.2f} {'':43s}  "
                     f"no op      {name}")
    return "\n".join(lines)
