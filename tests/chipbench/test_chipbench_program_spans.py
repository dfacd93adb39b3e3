"""The readers of the program's own spans and timestamps
(chipbench/program_spans.py and the layer_metrics that use it), each on
a hand-filled run: a Collector given spans, a stub or a small recorded
``reduced``. Without a device plane (``reduced`` None: a rehearsal)
and on a program that lacks the spans (the parent of the PR that
added them) every one returns None and does not raise."""
import os
import types

import pytest

import chipbench
from chipbench import trace
from chipbench.manifest import Manifest
from chipbench.spans import Collector, Span

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    chipbench.__file__)))
MANIFEST = Manifest(REPO)
ON_CHIP = object()                    # a device plane was there
WINDOW = (100.0, 104.0)

NEW = {
    "program_analysis_s": None, "jax_trace_s": None, "lower_s": None,
    "dispatch_overhead_ms.train": "train", "telemetry_ms.train": "train",
    "engine_host_ms.serve": "serve", "engine_queue_ms_p95.serve": "serve",
    "first_token_ms_p95.serve": "serve",
    "flash_fwd_time_share_pct.train": "train",
    "flash_bwd_time_share_pct.train": "train",
}


def _collector(spans):
    c = Collector()
    c.spans = [Span(name, start, dur, start + dur)
               for name, start, dur in spans]
    return c


def _read(metric, run):
    return MANIFEST.load_reader(metric).read(run)


def _run(kind, spans, reduced=ON_CHIP, **more):
    return dict(more, kind=kind, spans=_collector(spans), window=WINDOW,
                reduced=reduced)


SETUP_SPANS = [
    # before the window: the analyses, one after another
    ("compile::verify", 10.0, 0.5), ("compile::rewrite", 11.0, 1.0),
    ("compile::memory_plan", 12.0, 0.25), ("compile::cost_model", 13.0, 0.25),
    # an outer trace of 8 s with two inner jits' traces inside it, and a
    # separate eager op's: union 9 s, sum 12 s
    ("compile::jax_trace", 21.0, 1.0), ("compile::jax_trace", 23.0, 2.0),
    ("compile::jax_trace", 20.0, 8.0), ("compile::jax_trace", 40.0, 1.0),
    ("compile::lower", 28.0, 6.0), ("compile::lower", 41.0, 0.5),
    ("compile::backend", 34.0, 3.0),
    # inside the window: a recompile would be a fault, and is not set-up
    ("compile::lower", 101.0, 1.0), ("compile::jax_trace", 101.0, 1.0),
    ("compile::rewrite", 102.0, 1.0),
]


@pytest.mark.parametrize("metric, seconds", [
    ("program_analysis_s", 2.0), ("jax_trace_s", 9.0), ("lower_s", 6.5)])
def test_setup_readers_take_the_union_before_the_window(metric, seconds):
    run = _run("train", SETUP_SPANS)
    assert _read(metric, run) == pytest.approx(seconds)


STEP_SPANS = [
    ("pipeline::prepare", 100.1, 0.002), ("pipeline::dispatch", 100.11, 0.003),
    ("pipeline::commit", 100.12, 0.001), ("trainer::telemetry", 100.2, 0.0015),
    ("pipeline::prepare", 101.1, 0.004), ("pipeline::commit", 101.12, 0.001),
    ("pipeline::globalize_feed", 101.0, 0.002),
    ("trainer::telemetry", 101.2, 0.0005),
    # a warm-up step before the window, and one that ends after it
    ("pipeline::prepare", 98.5, 1.0), ("trainer::telemetry", 99.5, 0.2),
    ("pipeline::prepare", 103.9, 0.5),
]


@pytest.mark.parametrize("metric, ms", [
    ("dispatch_overhead_ms.train", (2 + 1 + 4 + 1 + 2) / 2),
    ("telemetry_ms.train", (1.5 + 0.5) / 2)])
def test_train_readers_average_over_the_measured_steps(metric, ms):
    run = _run("train", STEP_SPANS, steps=[(100.0, 101.0, 1.0),
                                            (101.0, 102.0, 1.0)])
    assert _read(metric, run) == pytest.approx(ms)
    assert _read(metric, dict(run, kind="serve")) is None
    assert _read(metric, dict(run, steps=[])) is None


def test_engine_host_is_the_iteration_less_its_device_waits():
    spans = [
        ("generation::iteration", 100.0, 0.030),
        ("pipeline::fetch_sync", 100.002, 0.004),      # a prefill's
        ("pipeline::fetch_sync", 100.010, 0.016),      # the decode's
        ("generation::iteration", 100.040, 0.020),
        ("pipeline::fetch_sync", 100.045, 0.012),
        # another thread's fetch between iterations: not the engine's
        ("pipeline::fetch_sync", 100.031, 0.005),
        # an iteration of the ramp, before the window
        ("generation::iteration", 99.0, 0.5),
    ]
    got = _read("engine_host_ms.serve", _run("serve", spans))
    assert got == pytest.approx(((30 - 4 - 16) + (20 - 12)) / 2)
    assert _read("engine_host_ms.serve", _run("train", spans)) is None


def _request(due, future):
    return types.SimpleNamespace(due=due, future=future)


def _future(enqueued, admitted, first):
    return types.SimpleNamespace(enqueued_at=enqueued, admitted_at=admitted,
                                 first_token_at=first)


def test_request_readers_use_the_futures_own_timestamps():
    import numpy as np
    requests = [_request(100.0 + i, _future(100.001 + i,
                                            100.001 + i + 0.001 * i,
                                            100.02 + i + 0.002 * i))
                for i in range(40)]
    requests.append(_request(150.0, None))                       # shed
    requests.append(_request(151.0, _future(151.0, None, None)))  # queued
    run = _run("serve", [], requests=requests)
    waits = [0.001 * i for i in range(40)]
    firsts = [0.02 + 0.002 * i for i in range(40)]
    assert _read("engine_queue_ms_p95.serve", run) == pytest.approx(
        np.percentile(waits, 95) * 1e3)
    assert _read("first_token_ms_p95.serve", run) == pytest.approx(
        np.percentile(firsts, 95) * 1e3)
    # the parent's futures carry no timestamps: nothing to read
    bare = _run("serve", [], requests=[
        _request(100.0, types.SimpleNamespace())])
    assert _read("engine_queue_ms_p95.serve", bare) is None
    assert _read("first_token_ms_p95.serve", bare) is None


def _device_trace(names):
    """Plain form of a trace: device 0 runs each named operation for
    1 ms, back to back."""
    events = [[n, 1e6 * i, 1e6] for i, n in enumerate(names)]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": trace.OPS_LINE, "events": events}]}]}


def test_flash_readers_tell_forward_from_backward_by_kernel_name():
    call = " custom-call:tpu_custom_call"
    named = trace.Reduced(_device_trace(
        ["jvp_flash_fwd_.1" + call, "flash_fwd.7" + call,
         "jvp_flash_bwd_dq_.1" + call, "flash_bwd_dkv.3" + call,
         "flash_bwd_dkv.4" + call, "fusion.1 fusion",
         "fused_lstm_fwd.2" + call, "flash_fwd_like.9 fusion",
         "add.1 add", "copy.1 copy"]), 1)
    run = _run("train", [], reduced=named)
    assert _read("flash_fwd_time_share_pct.train", run) == \
        pytest.approx(20.0)
    assert _read("flash_bwd_time_share_pct.train", run) == \
        pytest.approx(30.0)
    # the parent's kernels have no name (step_fn.24): nothing to read
    unnamed = trace.Reduced(_device_trace(
        ["step_fn.24" + call, "step_fn.25" + call, "fusion.1 fusion"]), 1)
    run = _run("train", [], reduced=unnamed)
    assert _read("flash_fwd_time_share_pct.train", run) is None
    assert _read("flash_bwd_time_share_pct.train", run) is None


@pytest.mark.parametrize("metric", sorted(NEW))
def test_nothing_to_read_is_none_and_never_raises(metric):
    kind = NEW[metric] or "train"
    every = SETUP_SPANS + STEP_SPANS + [
        ("generation::iteration", 100.0, 0.03)]
    requests = [_request(100.0, _future(100.0, 100.1, 100.2))]
    steps = [(100.0, 101.0, 1.0)]
    # no device plane (a rehearsal): None whatever the host heard
    assert _read(metric, _run(kind, every, reduced=None, steps=steps,
                              requests=requests)) is None
    # a device plane, but a program without the spans or timestamps
    on_chip = trace.Reduced(_device_trace(["fusion.1 fusion"]), 1)
    old = [("pipeline::dispatch", 100.1, 0.003),
           ("pipeline::fetch_sync", 100.2, 0.08),
           ("trace::step/3", 100.0, 0.1),
           ("generation::prefill[12]", 100.0, 0.01),
           ("generation::decode_step[512]", 100.02, 0.02)]
    assert _read(metric, _run(kind, old, reduced=on_chip, steps=steps,
                              requests=[_request(100.0, object())])) is None
    # the manifest's own smoke call
    assert _read(metric, {"spans": None}) is None


def test_manifest_lists_every_new_reader_after_the_old_ones():
    assert MANIFEST.problems() == []
    names = [m["name"] for m in MANIFEST.data["per_layer"]]
    assert names[0] == "input_wait_ms.train"
    assert set(names[10:]) == set(NEW) and len(names) == 10 + len(NEW)
    by_name = {m["name"]: m for m in MANIFEST.data["per_layer"]}
    cells = {w["name"] for w in MANIFEST.data["workloads"]}
    for name, kind in NEW.items():
        m = by_name[name]
        if kind is None:               # a set-up metric: every cell
            assert "workloads" not in m and m["moves"] == "setup_s"
        else:
            assert set(m["workloads"]) <= cells
            assert all(("serve" in c) == (kind == "serve")
                       for c in m["workloads"])
        assert "None" in MANIFEST.load_reader(name).__doc__
