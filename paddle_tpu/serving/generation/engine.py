"""GenerationEngine: continuous batching over the donated-KV decode
executables.

One driver thread owns the slot array. Each iteration is a decode-step
boundary:

  1. ADMIT — queued requests take free slots (one prefill each: full
     prompt forward writes the slot's KV rows and emits the first
     greedy token).
  2. STEP — one bucketed decode executable over the WHOLE slot array
     (single token per slot, cache-length bucket = smallest >= deepest
     active position + 1). Inactive slots ride along as padding.
  3. RETIRE — each slot's new token is delivered; slots finish
     independently on eos / max_new_tokens / max_seq_len and free
     immediately, so the next iteration's admit refills them without
     waiting for the rest of the batch (the continuous-batching
     property: a long request never convoys short ones).

``mode="reforward"`` is the ablation baseline: no KV cache, every step
re-runs the full causal forward over each row's entire history (cost
grows with the square of sequence length instead of linearly). The
token stream is greedy either way, so cached-vs-reforward outputs are
bit-comparable — tests/test_generation.py pins that identity.

The driver thread accounts for every instant of a pass: three spans
tile its timeline (``generation::idle_wait`` / ``collect`` /
``iteration``), and its own CPU clock is read beside the wall clock a
stretch of passes at a time, at most ``_STALL_S`` apart
(``stats()["loop"]``, ``paddle_tpu_decode_loop_*``): wall, less the
waits for the device, less CPU is the time the thread was neither
waiting for the device nor running, and a stretch with more than
``_STALL_S`` of it is a ``generation::stall`` span that carries the
evidence.

Failure containment mirrors the batch-serving engine: a step failure
records into the HealthMonitor (consecutive failures trip the breaker
OPEN → submit() sheds), and every in-flight request is retired with the
tokens it already completed (finish_reason="aborted") rather than
dropped — a breaker trip never loses delivered work.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import List, Optional

import numpy as np

from ... import profiler
from ...analysis.memory import publish_peak
from ...observability import attribution as obs_attr
from ...observability import thread_clock
from ...resilience import faults
from ...resilience import health as health_mod
from ...resilience.health import CircuitOpenError, HealthMonitor
from ..batcher import QueueFullError, ServingStopped
from .metrics import GenerationMetrics
from .model import bucket_for

__all__ = ["GenerationConfig", "GenerationResult", "GenerationFuture",
           "GenerationEngine"]

#: the driver thread's own clocks (its CPU seconds, the process's, its
#: context switches: a system call each, 6 us and a reschedule on a
#: sandboxed kernel such as the chip's host) are read where a pass ends
#: once this much time has gone by since the last reading — a STRETCH of
#: passes, at most twenty readings a second whatever the pass takes —
#: and a stretch whose thread was neither running nor waiting for the
#: device for longer than this is a generation::stall span (ten decode
#: steps of the benchmark's fastest cell)
_STALL_S = 0.05


class GenerationConfig:
    """Knobs for one engine.

    max_new_tokens:     default per-request generation budget (a submit
                        may lower, never raise past max_seq_len).
    queue_capacity:     backpressure bound on waiting (unslotted)
                        requests; submit() raises QueueFullError beyond
                        it.
    idle_wait_s:        driver sleep when no slot is active and no
                        request is queued.
    """

    def __init__(self, max_new_tokens: int = 16,
                 queue_capacity: int = 64, idle_wait_s: float = 0.05):
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.max_new_tokens = int(max_new_tokens)
        self.queue_capacity = int(queue_capacity)
        self.idle_wait_s = float(idle_wait_s)


class GenerationResult:
    """Delivered to the future when a request retires."""

    __slots__ = ("tokens", "finish_reason", "prompt_len")

    def __init__(self, tokens: List[int], finish_reason: str,
                 prompt_len: int):
        self.tokens = list(tokens)
        self.finish_reason = finish_reason
        self.prompt_len = prompt_len

    def __repr__(self):
        return (f"GenerationResult(tokens={self.tokens}, "
                f"finish_reason={self.finish_reason!r}, "
                f"prompt_len={self.prompt_len})")


class GenerationFuture:
    """Single-resolve handle for one generation request (same contract
    as batcher.ServingFuture: builtins TimeoutError, no cancel state
    machine).

    It also carries the request's life as the engine saw it, four
    ``time.perf_counter()`` readings (the clock of profiler spans),
    each None until reached: ``enqueued_at`` (accepted by submit),
    ``admitted_at`` (a slot taken, before its prefill),
    ``first_token_at`` (the first generated token delivered) and
    ``completed_at`` (the future resolved). A request that fails in the
    queue has only the first and the last."""

    def __init__(self):
        self._event = threading.Event()
        self._result: Optional[GenerationResult] = None
        self._exc: Optional[BaseException] = None
        self.enqueued_at: Optional[float] = None
        self.admitted_at: Optional[float] = None
        self.first_token_at: Optional[float] = None
        self.completed_at: Optional[float] = None

    def done(self) -> bool:
        return self._event.is_set()

    def set_result(self, result: GenerationResult):
        self._result = result
        self.completed_at = time.perf_counter()
        self._event.set()

    def set_exception(self, exc: BaseException):
        self._exc = exc
        self.completed_at = time.perf_counter()
        self._event.set()

    def result(self, timeout: Optional[float] = None) -> GenerationResult:
        if not self._event.wait(timeout):
            raise TimeoutError("generation did not complete in time")
        if self._exc is not None:
            raise self._exc
        return self._result


class _Request:
    __slots__ = ("prompt", "max_new_tokens", "future", "tokens")

    def __init__(self, prompt, max_new_tokens, future):
        self.prompt = list(int(t) for t in prompt)
        self.max_new_tokens = max_new_tokens
        self.future = future
        self.tokens: List[int] = []


class GenerationEngine:
    """Continuous-batching token server for one GenerationModel."""

    def __init__(self, model, config: Optional[GenerationConfig] = None,
                 metrics: Optional[GenerationMetrics] = None,
                 health: Optional[HealthMonitor] = None,
                 mode: str = "cached"):
        if mode not in ("cached", "reforward"):
            raise ValueError(f"mode must be 'cached' or 'reforward', "
                             f"got {mode!r}")
        self.model = model
        self.spec = model.spec
        self.config = config or GenerationConfig()
        self.metrics = metrics or GenerationMetrics()
        self.health = health or HealthMonitor()
        self.mode = mode
        self._slots: List[Optional[_Request]] = [None] * self.spec.slots
        # reforward-mode per-slot history: [slots, max_seq_len] tokens
        self._history = np.zeros(
            (self.spec.slots, self.spec.max_seq_len), np.int64)
        self._lengths = np.zeros(self.spec.slots, np.int64)
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._queue: deque = deque()
        self._thread: Optional[threading.Thread] = None
        self._started = False
        self._stopping = False
        self._drain = True
        # effective sequence ceiling: a step's bucket must cover the
        # deepest active position, so generation retires ("length")
        # before outgrowing the largest bucket this mode can run
        top = (self.spec.cache_buckets[-1] if mode == "cached"
               else self.spec.prompt_buckets[-1])
        self._max_len = min(self.spec.max_seq_len, top)
        self.metrics.slots_total.set(self.spec.slots)
        # the passes the driver thread made and the seconds it waited
        # for the device since its clocks were last read (_account,
        # _hear_device_wait)
        self._loop_ident = None
        self._passes = 0
        self._waited = 0.0

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        if self._started:
            raise RuntimeError("generation engine already started")
        self._thread = threading.Thread(target=self._driver_loop,
                                        name="generation-driver",
                                        daemon=True)
        self._started = True
        self._thread.start()
        return self

    def stop(self, drain: bool = True, timeout: Optional[float] = None):
        """Close the front door. drain=True (default) finishes every
        queued and in-flight request before the driver exits; False
        retires in-flight requests immediately with their completed
        tokens (finish_reason="aborted") and fails queued ones."""
        with self._wake:
            self._stopping = True
            self._drain = drain
            self._wake.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            if self._thread.is_alive():
                raise TimeoutError("generation driver still draining "
                                   "after timeout")
            self._thread = None

    # -- request path ------------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None
               ) -> GenerationFuture:
        if not self._started:
            raise RuntimeError("generation engine not started — call "
                               "engine.start() first")
        prompt = list(prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) > self.spec.prompt_buckets[-1]:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds largest prompt "
                f"bucket {self.spec.prompt_buckets[-1]}")
        budget = int(max_new_tokens if max_new_tokens is not None
                     else self.config.max_new_tokens)
        admit = self.health.allow_request()
        if not admit:
            self.metrics.shed("circuit_open")
            raise CircuitOpenError(
                "generation circuit is open (step failures tripped the "
                "breaker) — request shed; see engine.stats()['health']")
        try:
            fut = GenerationFuture()
            with self._wake:
                if self._stopping:
                    raise ServingStopped(
                        "generation engine is stopping")
                if len(self._queue) >= self.config.queue_capacity:
                    self.metrics.shed("queue_full")
                    raise QueueFullError(
                        f"generation queue at capacity "
                        f"({self.config.queue_capacity})")
                self._queue.append(_Request(prompt, budget, fut))
                fut.enqueued_at = time.perf_counter()
                self.metrics.requests.inc()
                self._wake.notify_all()
            return fut
        except BaseException:
            # admitted but never queued: hand back a consumed half-open
            # probe slot (only then — see ServingEngine.submit)
            if admit is health_mod.PROBE:
                self.health.release_probe()
            raise

    def generate(self, prompt, max_new_tokens: Optional[int] = None,
                 timeout: Optional[float] = None) -> GenerationResult:
        """Synchronous submit + wait."""
        return self.submit(prompt, max_new_tokens).result(timeout=timeout)

    # -- observability -----------------------------------------------------
    def stats(self):
        out = self.metrics.stats(executor=self.model.executor)
        with self._lock:
            out["queued"] = len(self._queue)
            out["active"] = sum(1 for s in self._slots if s is not None)
        out["mode"] = self.mode
        out["slots"] = self.spec.slots
        out["cache_buckets"] = list(self.spec.cache_buckets)
        out["started"] = self._started
        out["stopping"] = self._stopping
        out["health"] = self.health.snapshot()
        return out

    # -- driver ------------------------------------------------------------
    # The loop thread's timeline is tiled by three top-level spans:
    # generation::idle_wait (nothing queued, no live slot),
    # generation::collect (the rest of a pass's head: the queue's lock,
    # the drain, the stop checks) and generation::iteration (admit and
    # step). Its own clocks are read where a pass ends, a stretch of
    # passes at most _STALL_S long at a time (_account).
    def _driver_loop(self):
        self._loop_ident = threading.get_ident()
        profiler.add_event_listener(self._hear_device_wait)
        try:
            read = None     # the thread's clocks as last read
            while True:
                pending, read = self._collect(read)
                if pending is None:
                    self._account(read, done=0, flush=True)
                    return
                self._iteration(pending)
        finally:
            profiler.remove_event_listener(self._hear_device_wait)

    def _collect(self, read):
        """The head of a pass: the last pass into the account, the wait
        while there is nothing to do, the drain of the queue. Returns
        (the requests to admit, the thread's clocks as last read), or
        (None, ...) when the loop is over."""
        span = profiler.RecordEvent("generation::collect",
                                    cat=profiler.CAT_SERVING)
        span.__enter__()
        try:
            read = thread_clock.read() if read is None \
                else self._account(read)
            abort_now = False
            with self._wake:
                if self._idle():
                    # a pass that only waits counts nowhere: the account
                    # is closed before the wait and opened after it
                    self._account(read, done=0, flush=True)
                    span.__exit__()
                    with profiler.RecordEvent("generation::idle_wait",
                                              cat=profiler.CAT_SERVING):
                        while self._idle():
                            self._wake.wait(
                                timeout=self.config.idle_wait_s)
                    span.__enter__()
                    read = thread_clock.read()
                if self._stopping:
                    if not self._drain:
                        abort_now = True
                    elif not self._has_work():
                        return None, read  # drained
                pending = deque()
                while self._queue:
                    pending.append(self._queue.popleft())
            if abort_now:
                # outside the condition block: _abort_all re-takes the
                # queue lock to fail still-queued requests
                for req in pending:
                    self.metrics.retired("aborted")
                    req.future.set_exception(ServingStopped(
                        "generation engine stopped without drain"))
                self._abort_all(ServingStopped(
                    "generation engine stopped without drain"))
                return None, read
            return pending, read
        finally:
            span.__exit__()

    def _has_work(self) -> bool:
        """A queued request or a live slot (under _wake)."""
        return bool(self._queue) or any(s is not None for s in self._slots)

    def _idle(self) -> bool:
        return not self._stopping and not self._has_work()

    def _iteration(self, pending: deque):
        # every pass that gets here admits or steps
        with profiler.RecordEvent("generation::iteration",
                                  cat=profiler.CAT_SERVING):
            try:
                if pending:
                    with profiler.RecordEvent("generation::admit",
                                              cat=profiler.CAT_SERVING):
                        self._admit(pending)
                if any(s is not None for s in self._slots):
                    self._step()
            except BaseException as e:
                # device/step failure: the cache state of every
                # active slot is now suspect — retire them all with
                # the tokens they already completed, count the
                # failure toward the breaker, and keep the driver
                # alive (the breaker, not a dead thread, decides
                # whether to shed)
                self.health.record_failure(e)
                self._abort_all(e, reason="error")

    def _hear_device_wait(self, ev):
        """profiler listener: the loop thread's waits for the device,
        the executor's pipeline::fetch_sync spans as they close (a
        prefill's and a step's alike)."""
        if ev["tid"] == self._loop_ident and \
                ev["name"] == "pipeline::fetch_sync":
            self._waited += ev["dur"] * 1e-6

    def _account(self, read, done: int = 1, flush: bool = False):
        """``done`` passes ended since the thread's clocks were read
        (``read``). Once _STALL_S have gone by, or to ``flush``, the
        stretch is closed: the clocks are read again and its passes,
        its wall seconds less the device waits', the thread's CPU
        seconds and context switches go into the metrics; a
        generation::stall span when the thread neither ran nor waited
        for the device for more than _STALL_S of it. The CPU the thread
        burns INSIDE a wait (the fetched array's conversion, the
        runtime's own calls: 0.2 ms of a 2-ms wait on the chip's host)
        stays in ``cpu`` while the wait's wall leaves: taking it out is
        two more system calls a wait, 0.10 ms a pass there (PERF.md,
        PR 55), so wall less CPU reads low by it. Returns the clocks as
        last read."""
        self._passes += done
        if not flush and time.perf_counter() - read.wall < _STALL_S:
            return read
        end = thread_clock.read()
        passes, waited = self._passes, self._waited
        self._passes, self._waited = 0, 0.0
        if not passes:
            return end
        # (never under 0: a counter refuses it, and this is the
        # driver's thread)
        wall = max(end.wall - read.wall - waited, 0.0)
        cpu = end.cpu - read.cpu
        voluntary = involuntary = 0
        if end.voluntary is not None:
            voluntary = end.voluntary - read.voluntary
            involuntary = end.involuntary - read.involuntary
        self.metrics.loop_passes(passes, wall, cpu, waited, voluntary,
                                 involuntary)
        if wall - cpu > _STALL_S:
            # closed, so not on a device trace; the flight recorder's
            # ring hears it with every other listener
            profiler.emit(
                "generation::stall", read.wall, end.wall - read.wall,
                profiler.CAT_SERVING,
                {"passes": passes, "wall": wall, "cpu": cpu,
                 "device_wait": waited, "voluntary": voluntary,
                 "involuntary": involuntary,
                 "process_cpu": end.process_cpu - read.process_cpu})
        return end

    def _abort_all(self, exc: BaseException, reason: str = "aborted"):
        """Retire every in-flight slot with the tokens it completed (a
        trip/stop never drops delivered work) and fail the queue."""
        for i, req in enumerate(self._slots):
            if req is not None:
                self._retire(i, req, reason, finish="aborted")
        with self._lock:
            queued, self._queue = list(self._queue), deque()
        for req in queued:
            self.metrics.retired("aborted")
            req.future.set_exception(exc)

    # -- admit -------------------------------------------------------------
    def _admit(self, pending: deque):
        """Fill free slots from the queue; in cached mode each
        admission is one prefill (prompt forward + KV slot write + first
        token)."""
        requeue = []
        while pending:
            slot = next((i for i, s in enumerate(self._slots)
                         if s is None), None)
            if slot is None:
                requeue.extend(pending)
                pending.clear()
                break
            req = pending.popleft()
            req.future.admitted_at = time.perf_counter()
            if self.mode == "cached":
                t0 = time.monotonic()
                with profiler.RecordEvent(
                        f"generation::prefill[{len(req.prompt)}]",
                        cat=profiler.CAT_SERVING):
                    tok = self.model.run_prefill(req.prompt, slot)
                with profiler.RecordEvent("generation::telemetry",
                                          cat=profiler.CAT_SERVING):
                    self.metrics.prefills.inc()
                    self.metrics.prefill_seconds.record(
                        time.monotonic() - t0)
                    self.health.record_success()
                with profiler.RecordEvent("generation::deliver",
                                          cat=profiler.CAT_SERVING):
                    self._install(slot, req)
                    self._deliver_token(slot, req, tok)
            else:
                self._install(slot, req)
        if requeue:
            with self._lock:
                self._queue.extendleft(reversed(requeue))

    def _install(self, slot: int, req: _Request):
        self._slots[slot] = req
        p = len(req.prompt)
        self._history[slot, :] = 0
        self._history[slot, :p] = req.prompt
        self._lengths[slot] = p
        self._occupancy()

    def _occupancy(self):
        # the gauge moves where the occupancy does: an admission, a
        # retirement; a steady decode pass leaves it alone
        self.metrics.slots_active.set(
            sum(1 for s in self._slots if s is not None))

    # -- step --------------------------------------------------------------
    def _step(self):
        faults.fire("generation.step")
        if self.mode == "cached":
            self._step_cached()
        else:
            self._step_reforward()
        self.metrics.steps.inc()

    def _active(self):
        return [i for i, s in enumerate(self._slots) if s is not None]

    def _step_cached(self):
        """One donated-KV decode step: feed each active slot's last
        token at its own cache position; inactive slots ride as padding
        (they write garbage at position 0 of their row, which the next
        prefill into that row overwrites)."""
        # here and not at the top: importing paddle_tpu imports no Pallas
        from ...ops.pallas.decode_attention import kv_blocks
        with profiler.RecordEvent("generation::build_step",
                                  cat=profiler.CAT_SERVING):
            active = self._active()
            # feed position per slot = index the new token occupies
            positions = np.zeros(self.spec.slots, np.int64)
            tokens = np.zeros(self.spec.slots, np.int64)
            for i in active:
                positions[i] = self._lengths[i] - 1  # last token's
                tokens[i] = self._history[i, self._lengths[i] - 1]
            depth = int(max(positions[i] for i in active)) + 1
            bucket = bucket_for(depth, self.spec.cache_buckets)
            if bucket is None:  # deepest slot exceeded every bucket
                bucket = self.spec.cache_buckets[-1]
            # live cache rows per slot, this token counted; 0 where no
            # request is: attention reads nothing of an idle slot
            lengths = self._lengths
        t0 = time.monotonic()
        with profiler.RecordEvent(
                f"generation::decode_step[{bucket}]",
                cat=profiler.CAT_SERVING):
            next_tokens = self.model.run_decode(tokens, positions, bucket,
                                                lengths)
        self._observe_step(t0, kv_blocks(lengths, bucket))
        self._deliver(active, next_tokens)

    def _step_reforward(self):
        """Ablation baseline: full causal forward over every active
        row's whole history — what serving costs without the KV cache."""
        with profiler.RecordEvent("generation::build_step",
                                  cat=profiler.CAT_SERVING):
            active = self._active()
            depth = int(max(self._lengths[i] for i in active))
            bucket = bucket_for(depth, self.spec.prompt_buckets)
            if bucket is None:
                bucket = self.spec.prompt_buckets[-1]
            matrix = self._history[:, :bucket]
            lengths = np.maximum(self._lengths, 1)  # inactive rows: 1
        t0 = time.monotonic()
        with profiler.RecordEvent(
                f"generation::reforward_step[{bucket}]",
                cat=profiler.CAT_SERVING):
            next_tokens = self.model.run_full(matrix, lengths, bucket)
        self._observe_step(t0)
        self._deliver(active, next_tokens)

    def _deliver(self, active, next_tokens):
        with profiler.RecordEvent("generation::deliver",
                                  cat=profiler.CAT_SERVING):
            for i in active:
                self._deliver_token(i, self._slots[i],
                                    int(next_tokens[i]))

    def _observe_step(self, t0: float, blocks=None):
        """``blocks``: a cached step's (read, under the bound) cache
        blocks, by ops/pallas/decode_attention.py kv_blocks."""
        t1 = time.monotonic()
        with profiler.RecordEvent("generation::telemetry",
                                  cat=profiler.CAT_SERVING):
            self.health.record_success()
            self.metrics.step_seconds.record(t1 - t0)
            if blocks is not None:
                self.metrics.kv_blocks(*blocks)
                rows = self.model.last_expert_rows()
                if rows is not None:
                    self.metrics.expert_rows(rows)
            if obs_attr.attribution_enabled():
                cost = self.model.last_cost()
                peak = obs_attr.peak_flops()
                if cost is not None and cost.flops and t1 > t0 and peak:
                    self.metrics.set_mfu(cost.flops / peak / (t1 - t0),
                                         cost.flops)
            mem = self.model.last_memory()
            if mem is not None:
                publish_peak(self.metrics._attr_job, mem.peak_bytes)

    # -- retire ------------------------------------------------------------
    def _deliver_token(self, slot: int, req: _Request, tok: int):
        """Append one generated token to a slot's stream and retire the
        slot if the request is finished."""
        req.tokens.append(tok)
        if req.future.first_token_at is None:
            req.future.first_token_at = time.perf_counter()
        length = int(self._lengths[slot])
        if length < self.spec.max_seq_len:
            self._history[slot, length] = tok
        self._lengths[slot] = length + 1
        self.metrics.tokens.inc()
        reason = None
        if tok == self.spec.eos_id:
            reason = "eos"
        elif len(req.tokens) >= req.max_new_tokens:
            reason = "max_tokens"
        elif self._lengths[slot] >= self._max_len:
            reason = "length"
        if reason is not None:
            self._retire(slot, req, reason)

    def _retire(self, slot: int, req: _Request, reason: str,
                finish: Optional[str] = None):
        """Free the slot and resolve the request's future (the call
        that wakes its client) with the tokens it completed."""
        self._slots[slot] = None
        self._lengths[slot] = 0
        with profiler.RecordEvent("generation::retire",
                                  cat=profiler.CAT_SERVING):
            self._occupancy()
            self.metrics.retired(reason, req.future)
            req.future.set_result(GenerationResult(
                req.tokens, finish or reason, len(req.prompt)))
