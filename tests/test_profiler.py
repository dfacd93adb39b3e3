"""Profiler: host RecordEvents, each on its own thread's line and, while
a device trace runs, a TraceAnnotation on the trace's host plane — the
device trace is the merged timeline (reference: platform/profiler.h
event tables, device_tracer.cc:40-74 merging CUPTI device records into
one sorted output + timeline)."""
import json
import threading

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler


def _tiny_train(steps=3):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        pred = layers.fc(x, size=1)
        loss = layers.reduce_mean(layers.square_error_cost(pred, y))
        pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(16, 8).astype(np.float32),
            "y": rng.randn(16, 1).astype(np.float32)}
    for _ in range(steps):
        with profiler.RecordEvent("train_step"):
            exe.run(main, feed=feed, fetch_list=[loss])


def test_host_events_aggregate_and_export(tmp_path):
    profiler.start_profiler()
    _tiny_train()
    out = str(tmp_path / "host.json")
    agg = profiler.stop_profiler(profile_path=out)
    assert agg["train_step"]["calls"] == 3
    assert agg["train_step"]["total_us"] > 0
    trace = json.load(open(out))
    assert any(e["name"] == "train_step" for e in trace["traceEvents"])


def _heard(fn):
    """Every event closed while fn runs, as a listener hears them."""
    heard = []
    profiler.add_event_listener(heard.append)
    try:
        fn()
    finally:
        profiler.remove_event_listener(heard.append)
    return heard


def test_each_thread_records_its_own_tid_and_name():
    def work():
        with profiler.RecordEvent("on_worker"):
            pass

    def both():
        t = threading.Thread(target=work, name="span-worker")
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        with profiler.RecordEvent("on_main"):
            pass

    by_name = {e["name"]: e for e in _heard(both)}
    main, worker = by_name["on_main"], by_name["on_worker"]
    assert main["tid"] == threading.get_ident()
    assert worker["tid"] not in (0, main["tid"])
    assert worker["args"]["thread"] == "span-worker"
    assert main["args"]["thread"] == threading.current_thread().name


@pytest.mark.parametrize("tracing, raises", [
    (True, False), (True, True), (False, False)])
def test_a_span_enters_and_leaves_its_trace_annotation(monkeypatch,
                                                       tracing, raises):
    """While a jax.profiler trace runs every span is an annotation of
    it; with none running no annotation is made at all."""
    log = []
    monkeypatch.setattr(profiler, "_tracing", lambda: tracing)

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)

    def nested():
        with profiler.RecordEvent("outer"):
            with profiler.RecordEvent("inner"):
                if raises:
                    raise KeyError("inside")

    if raises:
        with pytest.raises(KeyError):
            nested()
    else:
        nested()
    assert log == ([("enter", "outer"), ("enter", "inner"),
                    ("exit", "inner"), ("exit", "outer")]
                   if tracing else [])


def test_tracing_follows_the_jax_profiler_session(tmp_path):
    assert profiler._tracing() is False
    with profiler.device_profiler(str(tmp_path / "xprof")):
        assert profiler._tracing() is True
        opened_inside = profiler.RecordEvent("spans_the_stop")
        opened_inside.__enter__()
    assert profiler._tracing() is False
    opened_inside.__exit__()        # its annotation outlived the trace


def test_a_listener_that_raises_breaks_nothing():
    def broken(ev):
        raise RuntimeError("listener bug")

    profiler.add_event_listener(broken)
    try:
        heard = _heard(lambda: _tiny_train(steps=1))
    finally:
        profiler.remove_event_listener(broken)
    # the span closed, and the listener after the broken one heard it
    assert any(e["name"] == "train_step" for e in heard)


@pytest.mark.parametrize("enabled, listener, recorded", [
    (True, False, 1), (False, True, 0), (False, False, 0)])
def test_emit_records_a_closed_span(enabled, listener, recorded):
    """profiler.emit is the way in for a duration known only once it is
    over (JAX's compile events): same record as a RecordEvent's."""
    heard = []
    if enabled:
        profiler.start_profiler()
    if listener:
        profiler.add_event_listener(heard.append)
    try:
        profiler.emit("compile::lower", 12.5, 0.25, profiler.CAT_COMPILE,
                      {"uid": 7})
    finally:
        profiler.remove_event_listener(heard.append)
        got = profiler.stop_profiler() if enabled else {}
    assert len(heard) == (1 if listener else 0)
    assert got.get("compile::lower", {}).get("calls", 0) == recorded
    for ev in heard:
        assert ev["ts"] == 12.5e6 and ev["dur"] == 0.25e6
        assert ev["cat"] == "compile" and ev["args"]["uid"] == 7
        assert ev["tid"] == threading.get_ident()


def test_device_profiler_trace_holds_the_program_spans(tmp_path):
    """device_profiler IS the merged timeline: a span open while the
    trace runs is on the trace's host plane, on the trace's clock."""
    from jax.profiler import ProfileData
    logdir = str(tmp_path / "xprof")
    with profiler.device_profiler(logdir):
        _tiny_train(steps=2)
    (path,) = list((tmp_path / "xprof").rglob("*.xplane.pb"))
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events}
    assert {"train_step", "pipeline::dispatch"} <= names
