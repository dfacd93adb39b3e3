"""Event-loop Trainer (reference: python/paddle/v2/trainer.py:37 — the
SGD class whose train() pumps a reader through forward/backward and fires
BeginPass/EndPass/BeginIteration/EndIteration events, v2/event.py; the
same loop fluid scripts hand-write around exe.run).

TPU-native: one Executor (or ParallelExecutor over a mesh) runs the
jit-compiled step; the event loop, metrics plumbing, periodic elastic
checkpointing (distributed/checkpoint.py), and test() evaluation live
here on the host."""
from __future__ import annotations

import contextlib
import time
from collections import deque
from typing import Callable, Dict, Iterable, List, Optional, Sequence

import numpy as np

from . import profiler
from .analysis.memory import publish_peak


class BeginPass:
    def __init__(self, pass_id):
        self.pass_id = pass_id


class EndPass:
    def __init__(self, pass_id, metrics=None):
        self.pass_id = pass_id
        self.metrics = metrics or {}


class BeginIteration:
    def __init__(self, pass_id, batch_id):
        self.pass_id = pass_id
        self.batch_id = batch_id


class EndIteration:
    """End-of-iteration event. `cost`/`metrics` may be LAZY: when the
    Trainer dispatched the step asynchronously it hands the event a
    StepResult instead of materialized values, and reading `.cost` (or
    `.metrics`) forces the device fetch at that point. A handler that
    skips them on non-logged iterations keeps the pipeline unblocked; a
    handler that always reads them gets the synchronous behaviour,
    values bit-identical either way."""

    def __init__(self, pass_id, batch_id, cost=None, metrics=None,
                 result=None, metric_names=()):
        self.pass_id = pass_id
        self.batch_id = batch_id
        self._cost = cost
        self._metrics = dict(metrics) if metrics is not None else None
        self._result = result
        self._metric_names = tuple(metric_names)

    @property
    def cost(self):
        if self._cost is None and self._result is not None:
            self._cost = _scalar_cost(self._result)
        return self._cost

    @property
    def metrics(self):
        if self._metrics is None:
            if self._result is not None:
                outs = self._result.fetches()
                self._metrics = {k: _dense(v) for k, v in
                                 zip(self._metric_names, outs[1:])}
            else:
                self._metrics = {}
        return self._metrics


class CheckpointConfig:
    """Periodic elastic checkpointing.

    retry:    optional resilience.RetryPolicy for the checkpoint I/O
              (each save's tmp-write phase retries as a unit).
    on_error: "warn" (default) — a save that still fails after retries
              is logged and counted (Trainer.checkpoint_failures) but
              does NOT kill training; the previous valid checkpoint
              remains the resume point. "raise" restores the old
              fail-stop behaviour.
    """

    def __init__(self, dirname: str, every_n_batches: int = 100,
                 max_keep: int = 3, retry=None, on_error: str = "warn"):
        if on_error not in ("warn", "raise"):
            raise ValueError(f"on_error must be 'warn' or 'raise', "
                             f"got {on_error!r}")
        self.dirname = dirname
        self.every_n_batches = every_n_batches
        self.max_keep = max_keep
        self.retry = retry
        self.on_error = on_error


class Trainer:
    """train() pumps reader batches through the program; each yielded
    batch is either a feed dict or a tuple routed through a DataFeeder
    built from `feed_order`."""

    def __init__(self, loss, main_program=None, startup_program=None,
                 executor=None, feed_order: Optional[Sequence] = None,
                 fetch_metrics: Optional[Dict[str, object]] = None,
                 checkpoint_config: Optional[CheckpointConfig] = None,
                 feeder_kwargs: Optional[dict] = None):
        from .framework import (default_main_program,
                                default_startup_program)
        from .executor import Executor

        self.loss = loss
        self.main_program = main_program or default_main_program()
        self.startup_program = startup_program or \
            default_startup_program()
        self.exe = executor or Executor()
        self.fetch_metrics = dict(fetch_metrics or {})
        self.checkpoint_config = checkpoint_config
        self._feeder = None
        if feed_order:
            from .data_feeder import DataFeeder
            vars_ = [self.main_program.global_block().var(n)
                     if isinstance(n, str) else n for n in feed_order]
            self._feeder = DataFeeder(vars_, **(feeder_kwargs or {}))
        self._started = False
        self.step = 0
        self.checkpoint_failures = 0
        self.last_checkpoint_error = None
        # streaming-input integration: the service train() is consuming
        # (cursor checkpointed beside the weights) and a restored
        # cursor waiting to seed the next service passed to train()
        self._input_service = None
        self._service_base = 0
        self._service_consumed = 0
        self._resume_input_state = None

    def _verify_programs(self):
        """Static verification of the (main, startup) pair, once at
        setup — the only gate that sees BOTH programs, so it is where
        uninitialized-persistable detection runs (a param the startup
        program never writes fails here with the var named, instead of
        as a scope KeyError mid-trace). Uses the cheap no-retrace shape
        pass: trainer programs come from the builder, which already
        stamped coverage/conflict markers. PADDLE_TPU_VERIFY=0 opts
        out."""
        from .analysis import verify_enabled, verify_program
        from .analysis.passes import fast_passes
        if not verify_enabled():
            return
        fetch = [self.loss.name] + [getattr(v, "name", str(v))
                                    for v in self.fetch_metrics.values()]
        feeds = [v.name for v in self._feeder.feed_vars] \
            if self._feeder is not None else None
        verify_program(
            self.main_program, startup=self.startup_program,
            feed_names=feeds, fetch_names=fetch,
            donate=getattr(self.exe, "donate_state", False),
            # train() always dispatches sync=False: a donated-fetch
            # hazard in fetch_metrics must fail HERE, not on the first
            # step after startup + checkpoint restore already ran
            async_dispatch=True,
            passes=fast_passes(with_uninit=True),
            program_label="trainer main program",
        ).raise_if_errors(context="Trainer setup")

    # -- lifecycle --------------------------------------------------------
    def start(self, resume: bool = True):
        """Run startup (param init), then restore the newest valid
        checkpoint if configured (elastic resume)."""
        self._verify_programs()
        self.exe.run(self.startup_program)
        if resume and self.checkpoint_config:
            from .distributed.checkpoint import load_checkpoint
            meta = load_checkpoint(self.checkpoint_config.dirname,
                                   main_program=self.main_program,
                                   executor=self.exe,
                                   retry=self.checkpoint_config.retry)
            if meta:
                self.step = int(meta.get("step", 0))
                # a streaming-input cursor saved with the checkpoint is
                # handed to the next StreamingInputService train() gets
                self._resume_input_state = meta.get("input_state")
        self._started = True
        return self

    def _to_feed(self, batch):
        if isinstance(batch, dict):
            return batch
        if self._feeder is None:
            raise ValueError(
                "reader yielded a tuple batch but no feed_order was given")
        return self._feeder.feed(batch)

    def _to_feed_device(self, batch):
        """_to_feed + host->device upload; runs on the prefetcher thread
        so the transfer overlaps the in-flight step's compute."""
        from .core.executor import device_feed
        return device_feed(self._to_feed(batch))

    # -- training loop ----------------------------------------------------
    def train(self, num_passes: int, reader: Callable[[], Iterable],
              event_handler: Optional[Callable] = None,
              steps_per_dispatch: int = 1, log_every: int = 1,
              prefetch: int = 0):
        """Event-loop training. steps_per_dispatch > 1 consumes K
        DISTINCT reader batches per compiled dispatch: the feeds are
        stacked along a leading K axis and Executor.run(iterations=K,
        stacked_feed=True) scans over them, so SGD semantics are
        unchanged from K=1 while per-dispatch overhead is paid once
        per K steps (the win on a high-RTT link). Events fire once per
        DISPATCH with the final batch's cost/metrics; self.step
        advances by the number of batches consumed. A short tail
        (fewer than K batches left in the pass) runs one batch at a
        time. Requires dense ndarray feeds of a fixed batch shape —
        ragged feeds fall back to per-batch dispatches.

        Every step is dispatched asynchronously (Executor.run
        sync=False); `log_every` sets how often the Trainer itself
        materializes cost/metrics. On logged dispatches (every
        `log_every`-th, default every one — the synchronous behaviour)
        EndIteration carries concrete values; in between it carries a
        lazy StepResult handle, the host never blocks on the device,
        and up to `log_every` undelivered results stay in flight.
        Trained weights are bit-identical for any `log_every` — only
        WHERE the host waits changes. `prefetch` > 0 additionally runs
        feed conversion + device upload for batch N+1 on a bounded
        background FeedPrefetcher (depth `prefetch`, 2 = classic
        double buffering) while batch N computes; incompatible with
        steps_per_dispatch > 1 (stacking needs host-side arrays).

        Checkpoint saves insert a device sync barrier first
        (Executor.synchronize), so a snapshot can never tear across an
        in-flight step.

        Observability (skipped entirely while the default
        MetricsRegistry is disabled — the process kill switch): each
        dispatch runs under a StepTrace root span (profiler events
        emitted inside — feed assembly, dispatch, RPC attempts — share
        one trace id per step), and the loop publishes
        paddle_tpu_train_steps_total / _step_seconds / _prefetch_depth
        (LIVE prefetch-queue occupancy; the configured depth is the
        separate _prefetch_depth_config gauge) to the metrics registry.
        step_seconds is host-side dispatch-to-dispatch wall time per
        batch: with async dispatch it measures sustained throughput,
        not device latency.

        `reader` may also be a reader.StreamingInputService: batches
        then come from the sharded multi-process input service, the
        service's delivered-batch cursor is checkpointed beside the
        weights, and a checkpoint resume re-seeds it (mid-epoch exact:
        no record replayed or skipped). Service epochs live in its
        config — call with num_passes=1."""
        from .observability import attribution as obs_attr
        from .observability import trace as obs_trace
        from .observability.registry import default_registry

        if not self._started:
            self.start()
        if getattr(reader, "is_streaming_input_service", False):
            # service-backed input: reader= is a StreamingInputService.
            # Its epochs live in the service config (use num_passes=1);
            # the delivered-batch cursor is checkpointed beside the
            # weights and a checkpoint restore re-seeds it, so resume
            # neither replays nor skips records.
            service = reader
            if self._resume_input_state is not None:
                service.restore(self._resume_input_state)
                self._resume_input_state = None
            reader = service.reader
            self._input_service = service
            self._service_base = service.delivered
            self._service_consumed = 0
        else:
            self._input_service = None
        user_handler = event_handler or (lambda e: None)

        def handler(event):
            # what the caller's own code costs the loop, step by step
            with profiler.RecordEvent("trainer::handler",
                                      cat=profiler.CAT_TRAINER):
                user_handler(event)

        fetch_names = list(self.fetch_metrics)
        fetch_list = [self.loss] + [self.fetch_metrics[k]
                                    for k in fetch_names]
        k = int(steps_per_dispatch)
        if k < 1:
            raise ValueError(
                f"steps_per_dispatch must be >= 1, got {k} — a zero "
                "dispatch would report cost 0.0 while training nothing")
        log_every = int(log_every)
        if log_every < 1:
            raise ValueError(
                f"log_every must be >= 1, got {log_every}")
        prefetch = int(prefetch)
        if prefetch < 0:
            raise ValueError(f"prefetch must be >= 0, got {prefetch}")
        if prefetch and k > 1:
            raise ValueError(
                "prefetch and steps_per_dispatch > 1 are mutually "
                "exclusive: stacking K batches needs host-side ndarray "
                "feeds, but the prefetcher uploads each batch to device")
        reg = default_registry()
        obs_on = reg.enabled
        # live attribution rides the SAME kill switches: the disabled
        # registry (process-wide off) or PADDLE_TPU_ATTRIBUTION=0
        attr_on = obs_on and obs_attr.attribution_enabled()
        if obs_on:
            m_steps = reg.counter(
                "paddle_tpu_train_steps_total",
                "Training steps (batches) dispatched by Trainer.train.")
            m_step_s = reg.histogram(
                "paddle_tpu_train_step_seconds",
                "Host-side wall time per training step "
                "(dispatch-to-dispatch / batches per dispatch; under "
                "async dispatch this is throughput, not device latency).")
            m_pref = reg.gauge(
                "paddle_tpu_train_prefetch_depth",
                "LIVE FeedPrefetcher queue occupancy sampled at each "
                "dispatch (0 = the loop is about to block on input — "
                "the starvation signal elastic input scaling watches; "
                "always 0 with prefetch=0 inline feeds).")
            m_pref.set(0)
            reg.gauge(
                "paddle_tpu_train_prefetch_depth_config",
                "Configured prefetch= depth of the current train() "
                "call (0 = inline feed assembly).").set(prefetch)
        if attr_on:
            m_mfu = None
            m_flops = obs_attr.model_flops_gauge(reg, "train")
            m_phase = obs_attr.phase_histogram(reg)
            # reset the phase window: events from start()/warmup must
            # not leak into the first step's breakdown
            obs_attr.drain_phases()

        def _stackable(feeds):
            if len(feeds) < 2:
                return None
            names = set(feeds[0])
            if any(set(f) != names for f in feeds[1:]):
                return None
            stacked = {}
            for n in names:
                vals = [f[n] for f in feeds]
                if not all(isinstance(v, np.ndarray) for v in vals):
                    return None
                if any(v.shape != vals[0].shape for v in vals[1:]):
                    return None
                stacked[n] = np.stack(vals)
            return stacked

        for pass_id in range(num_passes):
            handler(BeginPass(pass_id))
            costs = []
            # undelivered StepResults, oldest first; bounded at
            # log_every so a huge pass can't pin one fetch buffer per
            # step
            pending = deque()

            def _drain(keep: int):
                while len(pending) > keep:
                    costs.append(_scalar_cost(pending.popleft()))

            dispatch_id = 0
            prefetcher = None
            if prefetch:
                from .reader import FeedPrefetcher
                prefetcher = FeedPrefetcher(iter(reader()),
                                            convert=self._to_feed_device,
                                            depth=prefetch)
                feed_iter = iter(prefetcher)
            else:
                def _inline_feeds():
                    # un-prefetched path: reader + conversion run inline
                    # on the loop thread, so the wait is HOST-BLOCKED
                    # time (the A/B benchmark's sync-mode baseline)
                    raw_it = iter(reader())
                    while True:
                        with profiler.RecordEvent(
                                "pipeline::host_blocked",
                                cat=profiler.CAT_PIPELINE):
                            try:
                                batch = self._to_feed(next(raw_it))
                            except StopIteration:
                                return
                        yield batch

                feed_iter = _inline_feeds()
            t_prev = time.monotonic()
            try:
                while True:
                    # one StepTrace root span per dispatch: feed
                    # assembly, the dispatch itself, and any RPCs the
                    # handler issues all share this step's trace id.
                    # Gated with the metrics on the SAME toggle so a
                    # disabled registry is a full telemetry kill
                    # switch — and the overhead benchmark's "off" arm
                    # really is the uninstrumented loop.
                    with (obs_trace.step_trace(self.step) if obs_on
                          else contextlib.nullcontext()) as root:
                        if prefetcher is not None and root is not None:
                            # cross-thread span handoff: producer-side
                            # convert+upload work is stamped with the
                            # CURRENT step's span (the most recent
                            # dispatch — batch N+1 converts while step
                            # N computes)
                            prefetcher.adopt_span(root)
                        group = []
                        for _ in range(k):
                            try:
                                feed = next(feed_iter)
                                if k > 1:
                                    # accumulating K batches: snapshot
                                    # ndarray feeds NOW — readers like
                                    # multiprocess_batch_reader hand
                                    # out shared-memory views the
                                    # producer reuses once the
                                    # consumer advances
                                    feed = {n: (np.array(v) if
                                                isinstance(v, np.ndarray)
                                                else v)
                                            for n, v in feed.items()}
                                group.append(feed)
                            except StopIteration:
                                break
                        if not group:
                            # nothing dispatched: the span covered only
                            # the reader-exhaustion check, so drop its
                            # trace event rather than reporting a
                            # phantom N+1th step per pass
                            if root is not None:
                                root.discard()
                            break
                        handler(BeginIteration(pass_id, dispatch_id))
                        stacked = _stackable(group) if len(group) == k \
                            and k > 1 else None
                        if stacked is not None:
                            res = self.exe.run(self.main_program,
                                               feed=stacked,
                                               fetch_list=fetch_list,
                                               iterations=k,
                                               stacked_feed=True,
                                               sync=False)
                        else:
                            for i, feed in enumerate(group):
                                res = self.exe.run(self.main_program,
                                                   feed=feed,
                                                   fetch_list=fetch_list,
                                                   sync=False)
                                if i < len(group) - 1:
                                    # non-stackable k>1 fallback: only
                                    # the FINAL batch's result feeds
                                    # the event/cost plumbing, so
                                    # materialize the intermediates
                                    # here — fetch-time checks
                                    # (NaN/Inf) must cover every
                                    # batch, as the sync loop did
                                    res.fetches()
                        pending.append(res)
                        self.step += len(group)
                        if self._input_service is not None:
                            self._service_consumed += len(group)
                        logged = (dispatch_id + 1) % log_every == 0
                        ev = EndIteration(pass_id, dispatch_id,
                                          result=res,
                                          metric_names=fetch_names)
                        if logged:
                            ev.cost  # materialize: periodic sync point
                        handler(ev)
                        # logged dispatches flush everything in flight;
                        # others keep at most log_every results pending
                        # — but a checkpoint crossing drains fully
                        # first, so fetch-time checks (CHECK_NAN_INF)
                        # raise BEFORE a poisoned snapshot can publish
                        # as the newest resume point
                        if logged or self._checkpoint_due(len(group)):
                            _drain(0)
                        else:
                            _drain(log_every)
                        self._maybe_checkpoint(advanced=len(group))
                    if obs_on:
                        # what publishing the metrics costs a step
                        with profiler.RecordEvent(
                                "trainer::telemetry",
                                cat=profiler.CAT_TRAINER):
                            now = time.monotonic()
                            wall = now - t_prev
                            m_steps.inc(len(group))
                            m_step_s.record(wall / len(group))
                            m_pref.set(prefetcher.occupancy()
                                       if prefetcher is not None else 0)
                            t_prev = now
                            # static peak-HBM plan of THIS dispatch's
                            # executable (same result-not-executor rule as
                            # the cost read below)
                            mem = getattr(res, "memory", None)
                            if mem is not None:
                                publish_peak("train", mem.peak_bytes)
                            if attr_on:
                                # phase breakdown: measured host phases
                                # since the last dispatch + the device
                                # residual — the five phases of one step
                                # sum to its wall time (device clamps at 0
                                # when overlapped host work exceeds it)
                                phases = obs_attr.drain_phases()
                                host = sum(phases.values())
                                phases["device"] = max(0.0, wall - host)
                                for ph in obs_attr.PHASES:
                                    m_phase.labels(phase=ph).record(
                                        phases.get(ph, 0.0) / len(group))
                                # the dispatch's OWN cost off the result:
                                # exe.last_cost may already belong to a
                                # different program (an event handler
                                # calling trainer.test() runs the pruned
                                # eval clone on this same executor)
                                cost = getattr(res, "cost", None)
                                if cost is not None and cost.flops:
                                    step_s = wall / len(group)
                                    m_flops.set(float(cost.flops))
                                    peak = obs_attr.peak_flops()
                                    if step_s > 0 and peak:
                                        # registered on first use: a device
                                        # with no known peak leaves no
                                        # zero-valued mfu series behind
                                        if m_mfu is None:
                                            m_mfu = obs_attr.mfu_gauge(
                                                reg, "train")
                                        m_mfu.set(cost.flops / peak / step_s)
                    dispatch_id += 1
                    if len(group) < k:
                        break
            finally:
                if prefetcher is not None:
                    prefetcher.close()
            _drain(0)
            handler(EndPass(pass_id, {
                "mean_cost": float(np.mean(costs)) if costs else None}))

    def _checkpoint_due(self, advanced: int) -> bool:
        """Did the last `advanced` steps cross an every_n_batches
        multiple? ("crossed" rather than "== 0": with
        steps_per_dispatch > 1 the counter advances in strides and may
        never land exactly on a multiple.)"""
        cc = self.checkpoint_config
        return bool(cc) and (self.step // cc.every_n_batches
                             > (self.step - advanced)
                             // cc.every_n_batches)

    def _maybe_checkpoint(self, advanced: int = 1):
        cc = self.checkpoint_config
        if self._checkpoint_due(advanced):
            from .distributed.checkpoint import save_checkpoint
            # (save_checkpoint itself runs the Executor.synchronize
            # barrier before snapshotting, covering every caller)
            try:
                extra = None
                if self._input_service is not None:
                    # cursor of the TRAINED position (consumed count),
                    # not the prefetcher's read-ahead — resume
                    # re-produces the prefetched-but-untrained batches.
                    # Inside the try: a cursor-lookup failure is a
                    # checkpoint failure (warn path), not a run killer
                    extra = {"input_state":
                             self._input_service.state_for(
                                 self._service_base
                                 + self._service_consumed)}
                save_checkpoint(cc.dirname, step=self.step,
                                main_program=self.main_program,
                                executor=self.exe, max_keep=cc.max_keep,
                                extra_meta=extra, retry=cc.retry)
            except Exception as e:
                # checkpointing is off the training math path: a failed
                # save (after retries) must not kill the run — the last
                # valid checkpoint stays the resume point
                self.checkpoint_failures += 1
                self.last_checkpoint_error = e
                from .observability.registry import default_registry
                default_registry().counter(
                    "paddle_tpu_train_checkpoint_failures_total",
                    "Checkpoint saves that failed after retries "
                    "(training continued; previous checkpoint remains "
                    "the resume point).").inc()
                # flight-recorder trigger: the dump carries the events
                # and metrics leading up to the failed save
                from .observability.flight_recorder import record_failure
                record_failure("checkpoint_failure", exc=e,
                               context={"step": self.step,
                                        "dirname": cc.dirname})
                if cc.on_error == "raise":
                    raise
                import warnings
                warnings.warn(
                    f"checkpoint save at step {self.step} failed "
                    f"({e!r}); training continues, resume point is the "
                    "previous valid checkpoint", RuntimeWarning)

    # -- evaluation -------------------------------------------------------
    def test(self, reader: Callable[[], Iterable],
             fetch_list: Optional[List] = None) -> Dict[str, float]:
        """Mean of loss (+ metrics) over a test reader — no optimizer ops
        run because the fetches are computed on an inference-pruned clone
        (reference: v2 SGD.test, trainer.py:209)."""
        from .core.executor import STEP_VAR
        from .core.scope import global_scope
        from .io import _prune

        fetch_list = fetch_list or [self.loss]
        names = [getattr(v, "name", v) for v in fetch_list]
        pruned = _prune(self.main_program, [], names)
        totals = {n: [] for n in names}
        scope = global_scope()
        step_before = scope.find(STEP_VAR)
        try:
            for batch in reader():
                feed = self._to_feed(batch)
                outs = self.exe.run(pruned, feed=feed, fetch_list=names)
                for n, v in zip(names, outs):
                    totals[n].append(
                        np.asarray(_dense(v), np.float64).mean())
        finally:
            # evaluation must not advance the LR-schedule step counter
            if step_before is not None:
                scope.set(STEP_VAR, step_before)
        return {n: float(np.mean(vs)) if vs else float("nan")
                for n, vs in totals.items()}

    def save_params(self, dirname: str):
        from . import io as pt_io
        pt_io.save_params(self.exe, dirname, self.main_program)

    def save_inference_model(self, dirname: str, feed_names, targets):
        from . import io as pt_io
        pt_io.save_inference_model(dirname, feed_names, targets, self.exe,
                                   main_program=self.main_program)


def _dense(v):
    return v.data if hasattr(v, "data") else v


def _scalar_cost(outs) -> float:
    """First fetched value (the loss) as a python float — the one cost
    extraction shared by EndIteration.cost and the pass-mean plumbing."""
    return float(np.asarray(_dense(outs[0])).reshape(-1)[0])
