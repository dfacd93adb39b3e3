"""Always-on performance attribution (ISSUE 6): static cost model,
live MFU + step-phase telemetry, and the failure flight recorder.

Covers the acceptance criteria that are testable on the CPU backend:
a single registry read of a running trainer reports a nonzero
``paddle_tpu_mfu`` gauge and a step-phase breakdown whose phase sum
equals step wall time; an injected ``checkpoint.write`` fault and a NaN
fetch each produce a loadable chrome-trace flight-recorder bundle,
while a clean run writes nothing.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, observability as obs, profiler
from paddle_tpu.analysis import cost_model
from paddle_tpu.observability import attribution
from paddle_tpu.observability import flight_recorder as frm
from paddle_tpu.observability import trace
from paddle_tpu.resilience import FaultInjector
from paddle_tpu.trainer import CheckpointConfig, Trainer


@pytest.fixture
def fresh_registry():
    prev = obs.set_default_registry(obs.MetricsRegistry())
    yield obs.default_registry()
    obs.set_default_registry(prev)


@pytest.fixture
def peak_flops_flag(monkeypatch):
    """The CPU backend is in no peak table, so a test that reads
    paddle_tpu_mfu names the peak it divides by."""
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "197e12")


@pytest.fixture
def fresh_recorder(tmp_path):
    """Point the process-default flight recorder at a private tmp dir
    so this test sees exactly its own dumps."""
    rec = frm.FlightRecorder(dump_dir=str(tmp_path / "flightrec"),
                             min_interval_s=0.0).enable()
    prev = frm.set_flight_recorder(rec)
    yield rec
    rec.disable()
    frm.set_flight_recorder(prev)


def _build_mlp():
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = 0
    with pt.program_guard(main, startup):
        x = layers.data("x", [8])
        label = layers.data("label", [1])
        pred = layers.fc(x, size=4)
        loss = layers.mean(layers.square(pred - label))
        pt.optimizer.SGDOptimizer(learning_rate=0.05).minimize(loss)
    return main, startup, loss


def _reader(n=6, bs=4):
    def read():
        rng = np.random.RandomState(0)
        for _ in range(n):
            yield {"x": rng.rand(bs, 8).astype(np.float32),
                   "label": rng.rand(bs, 1).astype(np.float32)}
    return read


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------
def test_cost_model_counts_matmul_exactly():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [13])
        y = layers.data("y", [1])
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square(pred - y))
        pt.optimizer.SGDOptimizer(learning_rate=0.01).minimize(loss)
    cost = cost_model.program_cost(
        main, feed_shapes={"x": (4, 13), "y": (4, 1)})
    assert cost.batch == 4  # bound from the feed's leading dim
    (mul,) = [c for c in cost.ops if c.op_type == "mul"]
    assert mul.flops == 2 * 4 * 13 * 1 and mul.exact
    # the fc weight is read: program param bytes include w (13x1 f32)
    assert cost.param_bytes >= 13 * 1 * 4
    assert cost.flops > mul.flops  # backward + optimizer on top
    assert cost.bytes_accessed > 0 and cost.unresolved == 0


def test_cost_model_counts_conv_exactly():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        img = layers.data("img", [3, 8, 8])
        c = layers.conv2d(img, num_filters=4, filter_size=3, padding=1,
                          bias_attr=False)
    cost = cost_model.program_cost(main, feed_shapes={"img": (2, 3, 8, 8)})
    (conv,) = [c_ for c_ in cost.ops if c_.op_type == "conv2d"]
    # 2 * out_numel * (Cin/groups * kh * kw)
    assert conv.flops == 2 * (2 * 4 * 8 * 8) * (3 * 3 * 3) and conv.exact


def test_cost_model_vjp_doubles_forward():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [13])
        y = layers.data("y", [1])
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square(pred - y))
        pt.optimizer.SGDOptimizer(learning_rate=0.01).minimize(loss)
    cost = cost_model.program_cost(
        main, feed_shapes={"x": (4, 13), "y": (4, 1)})
    (mul,) = [c for c in cost.ops if c.op_type == "mul"]
    mul_vjps = [c for c in cost.ops if c.op_type == "__vjp__"
                and c.note and "mul" in c.note]
    assert mul_vjps and mul_vjps[0].flops == 2 * mul.flops

    # the attention op has its own rule (two seq^2 contractions and the
    # softmax), and its grad op books twice that
    from paddle_tpu.layer_helper import LayerHelper
    B, H, S, D = 2, 2, 8, 4
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        q = layers.fc(layers.data("q", [H, S, D]), size=D,
                      num_flatten_dims=3, bias_attr=False)
        helper = LayerHelper("sdpa")
        out = helper.create_tmp_variable("float32")
        helper.append_op(type="scaled_dot_product_attention",
                         inputs={"Q": q, "K": q, "V": q},
                         outputs={"Out": out})
        pt.optimizer.SGDOptimizer(learning_rate=0.01).minimize(
            layers.mean(out))
    cost = cost_model.program_cost(main, feed_shapes={"q": (B, H, S, D)})
    (sdpa,) = [c for c in cost.ops
               if c.op_type == "scaled_dot_product_attention"]
    assert sdpa.exact
    assert sdpa.flops == 4 * B * H * S * S * D + 5 * B * H * S * S
    (sdpa_vjp,) = [c for c in cost.ops if c.op_type == "__vjp__"
                   and c.note and "scaled_dot" in c.note]
    assert sdpa_vjp.flops == 2 * sdpa.flops


def test_cost_model_pass_attaches_report_cost():
    main, startup, loss = _build_mlp()
    from paddle_tpu.analysis import ProgramVerifier
    report = ProgramVerifier(passes=["cost_model"]).verify(
        main, fetch_names=[loss.name])
    assert report.cost is not None and report.cost.flops > 0
    assert "flops" in report.cost.table()


def test_executor_attaches_cost_on_compile_miss():
    main, startup, loss = _build_mlp()
    exe = pt.Executor()
    exe.run(startup)
    feed = {"x": np.zeros((4, 8), np.float32),
            "label": np.zeros((4, 1), np.float32)}
    exe.run(main, feed=feed, fetch_list=[loss])
    assert exe.last_cost is not None and exe.last_cost.flops > 0
    assert exe.last_cost.batch == 4
    assert exe.cost_for(main) is exe.last_cost
    table = exe.cost_table()
    assert table and "mul" in table
    # a cache HIT re-exposes the same attached cost
    prev = exe.last_cost
    exe.run(main, feed=feed, fetch_list=[loss])
    assert exe.last_cost is prev


# ---------------------------------------------------------------------------
# live MFU + phase breakdown
# ---------------------------------------------------------------------------
def test_peak_flops_is_keyed_by_device_kind(monkeypatch):
    """One table, keyed by device_kind; the flag overrides it; a device
    in neither has NO peak (not the v5e's), and a malformed flag raises
    instead of quietly becoming the default."""
    monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS", raising=False)
    assert attribution.PEAK_FLOPS_BY_DEVICE_KIND["TPU v5 lite"] == 197e12
    assert attribution.peak_flops() is None          # device_kind "cpu"
    with pytest.raises(RuntimeError, match="no peak FLOP/s known"):
        attribution.require_peak_flops()
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "1e12")
    assert attribution.peak_flops() == attribution.require_peak_flops() \
        == 1e12
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "fast")
    with pytest.raises(ValueError):
        attribution.peak_flops()


def test_trainer_publishes_no_mfu_for_an_unknown_device(fresh_registry,
                                                        monkeypatch):
    """No peak, no paddle_tpu_mfu series — absent, not a number
    computed against some other device's peak. The rest of the
    attribution (model FLOPs, phases) still publishes."""
    monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS", raising=False)
    main, startup, loss = _build_mlp()
    Trainer(loss, main_program=main, startup_program=startup).train(
        num_passes=1, reader=_reader())
    assert fresh_registry.get("paddle_tpu_mfu") is None
    assert fresh_registry.get("paddle_tpu_model_flops") \
        .labels(job="train").value > 0
    assert fresh_registry.get("paddle_tpu_step_phase_seconds") is not None


def test_trainer_publishes_mfu_and_phase_breakdown(fresh_registry,
                                                   peak_flops_flag):
    """Acceptance: a registry read of a running trainer reports a
    nonzero paddle_tpu_mfu and a phase breakdown whose phase sum equals
    total step wall time (device is the residual, so the identity holds
    by construction — this asserts the wiring doesn't drop phases)."""
    main, startup, loss = _build_mlp()
    trainer = Trainer(loss, main_program=main, startup_program=startup)
    trainer.train(num_passes=2, reader=_reader())

    reg = fresh_registry
    mfu = reg.get("paddle_tpu_mfu").labels(job="train").value
    flops = reg.get("paddle_tpu_model_flops").labels(job="train").value
    assert mfu > 0 and flops > 0
    # gauge consistency: mfu == flops / peak / step_s for the LAST step;
    # against the mean step time it stays within the same order
    (_, step_h), = reg.get("paddle_tpu_train_step_seconds").samples()
    assert step_h.count == 12

    phase_fam = reg.get("paddle_tpu_step_phase_seconds")
    by_phase = {key[0]: child for key, child in phase_fam.samples()}
    assert set(by_phase) == set(attribution.PHASES)
    for child in by_phase.values():
        assert child.count == 12  # every phase recorded every dispatch
    phase_total = sum(child.sum for child in by_phase.values())
    wall_total = step_h.sum
    # identity up to the device>=0 clamp and drain-boundary leakage
    assert phase_total == pytest.approx(wall_total, rel=0.25)
    # this tiny CPU net is dispatch/host-dominated, never 100% device
    assert by_phase["dispatch"].sum > 0


def test_attribution_kill_switch(fresh_registry):
    attribution.set_attribution_enabled(False)
    try:
        main, startup, loss = _build_mlp()
        trainer = Trainer(loss, main_program=main,
                          startup_program=startup)
        trainer.train(num_passes=1, reader=_reader(n=2))
        assert fresh_registry.get("paddle_tpu_mfu") is None
        assert fresh_registry.get("paddle_tpu_step_phase_seconds") is None
        # base telemetry still publishes
        assert fresh_registry.get("paddle_tpu_train_steps_total") is not None
    finally:
        attribution.set_attribution_enabled(None)


def test_step_result_carries_dispatch_cost():
    """Async consumers (serving workers sharing one executor) read the
    dispatch's own cost off the StepResult — the executor-global
    last_cost may already belong to a later dispatch."""
    main, startup, loss = _build_mlp()
    exe = pt.Executor()
    exe.run(startup)
    feed = {"x": np.zeros((4, 8), np.float32),
            "label": np.zeros((4, 1), np.float32)}
    res = exe.run(main, feed=feed, fetch_list=[loss], sync=False)
    assert res.cost is exe.last_cost and res.cost.flops > 0
    res.fetches()


def test_attribution_env_flip_reinstalls_listener(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_ATTRIBUTION", "0")
    attribution.set_attribution_enabled(None)  # re-sync from env: off
    assert attribution._phase_listener not in profiler._event_listeners
    # a post-import 0 -> 1 env flip must self-heal, or the MFU gauges
    # publish alongside an all-device (empty-bucket) phase breakdown
    monkeypatch.setenv("PADDLE_TPU_ATTRIBUTION", "1")
    assert attribution.attribution_enabled()
    assert attribution._phase_listener in profiler._event_listeners


def test_serving_engine_publishes_mfu(tmp_path, fresh_registry,
                                      peak_flops_flag):
    from paddle_tpu import serving

    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = 0
    with pt.program_guard(main, startup):
        x = layers.data("x", [8])
        pred = layers.fc(x, size=4)
    exe = pt.Executor()
    exe.run(startup)
    pt.io.save_inference_model(str(tmp_path), ["x"], [pred], exe,
                               main_program=main)
    model = serving.load(str(tmp_path))
    engine = model.serve(serving.BatchingConfig(max_batch_size=2,
                                                max_latency_ms=1.0))
    engine.start(warmup=False)
    try:
        engine.predict({"x": np.zeros((1, 8), np.float32)}, timeout=30)
    finally:
        engine.stop()
    stats = engine.stats()
    assert stats["mfu"] > 0 and stats["model_flops"] > 0
    job = f"engine_{engine.metrics.engine_label}"
    assert fresh_registry.get("paddle_tpu_mfu").labels(job=job).value > 0


def test_serving_engine_kill_switch_no_mfu_series(tmp_path,
                                                  fresh_registry):
    """With attribution off, an engine must not leave a zero-valued
    paddle_tpu_mfu series behind — absent data, not a permanent 0."""
    from paddle_tpu import serving

    attribution.set_attribution_enabled(False)
    try:
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [8])
            pred = layers.fc(x, size=4)
        exe = pt.Executor()
        exe.run(startup)
        pt.io.save_inference_model(str(tmp_path), ["x"], [pred], exe,
                                   main_program=main)
        model = serving.load(str(tmp_path))
        engine = model.serve(serving.BatchingConfig(max_batch_size=2,
                                                    max_latency_ms=1.0))
        engine.start(warmup=False)
        try:
            engine.predict({"x": np.zeros((1, 8), np.float32)},
                           timeout=30)
        finally:
            engine.stop()
        assert fresh_registry.get("paddle_tpu_mfu") is None
        assert engine.stats()["mfu"] == 0.0
    finally:
        attribution.set_attribution_enabled(None)


# ---------------------------------------------------------------------------
# cross-thread trace propagation (the closed KNOWN_GAPS boundary)
# ---------------------------------------------------------------------------
def test_prefetcher_producer_stamps_adopted_span():
    import threading

    from paddle_tpu.reader import FeedPrefetcher

    gate = threading.Event()

    def batches():
        gate.wait(5.0)  # hold the producer until the span is adopted
        yield 1
        yield 2

    profiler.start_profiler()
    try:
        with trace.step_trace(11) as root:
            pf = FeedPrefetcher(batches(), convert=lambda b: b * 10,
                                fire_faults=False)
            pf.adopt_span(root)
            gate.set()
            got = list(pf)
        assert got == [10, 20]
    finally:
        profiler.stop_profiler()
    fills = [e for e in profiler.events()
             if e["name"] == "pipeline::prefetch_fill"]
    assert len(fills) == 2, fills
    for e in fills:
        # producer-thread events carry the OWNING step's ids even
        # though the producer has no contextvar of its own
        assert e["args"]["trace_id"] == root.trace_id
        assert e["args"]["span_id"] == root.span_id


def test_lazy_fetch_stamps_owning_step_span():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [2])
        out = layers.scale(x, scale=2.0)
    exe = pt.Executor()
    exe.run(startup)
    profiler.start_profiler()
    try:
        with trace.step_trace(5) as owning:
            res = exe.run(main, feed={"x": np.ones((1, 2), np.float32)},
                          fetch_list=[out], sync=False)
        with trace.step_trace(6):
            # materialized under a DIFFERENT step's span: the event
            # must still be stamped with the OWNING step's ids
            res.fetches()
    finally:
        profiler.stop_profiler()
    fetch_evs = [e for e in profiler.events()
                 if e["name"] == "pipeline::fetch_sync"]
    assert fetch_evs
    assert fetch_evs[-1]["args"]["trace_id"] == owning.trace_id
    assert fetch_evs[-1]["args"]["span_id"] == owning.span_id


def test_serving_worker_opens_batch_span(tmp_path):
    from paddle_tpu import serving

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        pred = layers.fc(x, size=2)
    exe = pt.Executor()
    exe.run(startup)
    pt.io.save_inference_model(str(tmp_path), ["x"], [pred], exe,
                               main_program=main)
    model = serving.load(str(tmp_path))
    engine = model.serve(serving.BatchingConfig(max_batch_size=2,
                                                max_latency_ms=1.0))
    engine.start(warmup=False)
    profiler.start_profiler()
    try:
        engine.predict({"x": np.zeros((1, 4), np.float32)}, timeout=30)
    finally:
        profiler.stop_profiler()
        engine.stop()
    runs = [e for e in profiler.events()
            if e["name"].startswith("serving::batch_run")]
    assert runs, "no batch_run event recorded"
    # worker thread had no inherited context: the engine opened a fresh
    # root span per batch and the run event carries its ids
    assert runs[-1].get("args", {}).get("trace_id")


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
def _assert_valid_bundle(path, reason):
    with open(os.path.join(path, "trace.json")) as f:
        tr = json.load(f)
    assert isinstance(tr["traceEvents"], list)
    for ev in tr["traceEvents"]:
        assert ev["ph"] == "X" and "dur" in ev and "ts" in ev
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    assert meta["reason"] == reason
    assert meta["num_events"] == len(tr["traceEvents"])
    return tr, meta


def test_flight_recorder_silent_on_clean_run(fresh_recorder):
    main, startup, loss = _build_mlp()
    trainer = Trainer(loss, main_program=main, startup_program=startup)
    trainer.train(num_passes=1, reader=_reader(n=3))
    assert fresh_recorder.dumps() == []


def test_flight_recorder_dumps_on_nan_fetch(fresh_recorder,
                                            monkeypatch):
    from paddle_tpu.core import executor as core_exec
    monkeypatch.setattr(core_exec, "CHECK_NAN_INF", True)

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [2])
        out = layers.scale(x, scale=2.0)
    exe = pt.Executor()
    exe.run(startup)
    with pytest.raises(FloatingPointError):
        exe.run(main, feed={"x": np.array([[np.nan, 1.0]], np.float32)},
                fetch_list=[out])
    (dump,) = fresh_recorder.dumps()
    assert "nan_fetch" in dump
    tr, meta = _assert_valid_bundle(dump, "nan_fetch")
    assert meta["context"]["var"] == out.name
    assert meta["exception"] and "NaN" in meta["exception"]
    # the ring buffer captured the dispatch leading up to the failure,
    # with no profiler session active
    assert any(e["name"] == "pipeline::dispatch"
               for e in tr["traceEvents"])


@pytest.mark.chaos
def test_flight_recorder_dumps_on_checkpoint_fault(fresh_recorder,
                                                   tmp_path):
    """Acceptance (chaos): an injected checkpoint.write fault produces
    a loadable chrome-trace bundle exactly when the fault fires."""
    main, startup, loss = _build_mlp()
    trainer = Trainer(
        loss, main_program=main, startup_program=startup,
        checkpoint_config=CheckpointConfig(
            str(tmp_path / "ckpt"), every_n_batches=2, on_error="warn"))
    with FaultInjector(seed=1) as fi:
        fi.on("checkpoint.write", raises=IOError)
        with pytest.warns(RuntimeWarning):
            trainer.train(num_passes=1, reader=_reader(n=4))
        assert fi.triggered("checkpoint.write") >= 1
    assert trainer.checkpoint_failures >= 1
    dumps = [d for d in fresh_recorder.dumps()
             if "checkpoint_failure" in d]
    assert len(dumps) == trainer.checkpoint_failures
    _tr, meta = _assert_valid_bundle(dumps[0], "checkpoint_failure")
    assert "injected fault" in meta["exception"]
    assert meta["metrics"] and \
        "paddle_tpu_train_steps_total" in meta["metrics"]


def test_flight_recorder_dumps_on_verification_error(fresh_recorder):
    from paddle_tpu.analysis import (Diagnostic, Severity,
                                     VerificationError, VerifyReport)
    report = VerifyReport(program_label="broken prog")
    report.add(Diagnostic(Severity.ERROR, "dangling-input", "boom"))
    with pytest.raises(VerificationError):
        report.raise_if_errors(context="test gate")
    (dump,) = fresh_recorder.dumps()
    _tr, meta = _assert_valid_bundle(dump, "verification_error")
    assert meta["context"]["program"] == "broken prog"


def test_flight_recorder_rate_limit_and_prune(tmp_path):
    rec = frm.FlightRecorder(dump_dir=str(tmp_path), max_dumps=3,
                             min_interval_s=3600.0).enable()
    try:
        assert rec.trigger("nan_fetch") is not None
        # same reason inside the interval: rate-limited
        assert rec.trigger("nan_fetch") is None
        # other reasons still dump; pruning keeps the newest max_dumps
        for reason in ("checkpoint_failure", "circuit_open",
                       "verification_error"):
            assert rec.trigger(reason) is not None
        assert len(rec.dumps()) == 3
    finally:
        rec.disable()


def test_default_recorder_is_live_at_import():
    """The process default must be capturing BEFORE the first failure:
    a lazily-built default would dump an empty ring for the first
    (often only) failure of the process."""
    rec = frm.flight_recorder()
    assert rec.enabled
    with profiler.RecordEvent("flightrec::liveness_probe"):
        pass
    assert any(e["name"] == "flightrec::liveness_probe"
               for e in rec.events())


def test_flight_recorder_failed_write_releases_rate_limit_slot(tmp_path):
    """A dump whose write fails must not consume the per-reason
    rate-limit slot nor leave a .tmp orphan behind."""
    rec = frm.FlightRecorder(dump_dir=str(tmp_path),
                             min_interval_s=3600.0).enable()
    try:
        rec._on_event({"name": object()})  # not JSON-serializable
        assert rec.trigger("nan_fetch") is None
        assert not [d for d in os.listdir(tmp_path)
                    if d.endswith(".tmp")]
        with rec._lock:
            rec._events.clear()
        # the failed attempt did not burn the 1/h slot
        assert rec.trigger("nan_fetch") is not None
    finally:
        rec.disable()


def test_flight_recorder_disabled_is_silent(tmp_path):
    rec = frm.FlightRecorder(dump_dir=str(tmp_path))
    assert not rec.enabled
    with profiler.RecordEvent("x"):
        pass
    assert rec.events() == []           # no listener installed
    assert rec.trigger("nan_fetch") is None
    assert rec.dumps() == []
