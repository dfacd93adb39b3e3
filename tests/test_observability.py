"""paddle_tpu.observability: metrics registry, step tracing, telemetry
endpoint — plus the acceptance scrape (a running trainer + serving
engine exposed through one GET /metrics in valid Prometheus text
exposition format)."""
import json
import re
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, observability as obs, profiler, serving
from paddle_tpu.observability import trace
from paddle_tpu.observability.registry import (METRIC_NAME_RE, Histogram,
                                               MetricsRegistry)
from paddle_tpu.trainer import Trainer


@pytest.fixture
def fresh_registry():
    """Isolate a test's metrics in a fresh default registry (the
    process default accumulates across the whole session)."""
    prev = obs.set_default_registry(obs.MetricsRegistry())
    yield obs.default_registry()
    obs.set_default_registry(prev)


# ---------------------------------------------------------------------------
# registry basics
# ---------------------------------------------------------------------------
def test_registry_validates_names_and_help():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("bad_name_total", "help")
    with pytest.raises(ValueError):
        reg.counter("paddle_tpu_UpperCase", "help")
    with pytest.raises(ValueError):
        reg.counter("paddle_tpu_ok_total", "")
    with pytest.raises(ValueError):
        reg.gauge("paddle_tpu_g", "help", labelnames=("0bad",))
    c = reg.counter("paddle_tpu_ok_total", "help")
    assert reg.counter("paddle_tpu_ok_total", "help") is c
    # re-registration with ANY conflicting declaration must fail loudly
    with pytest.raises(ValueError):
        reg.gauge("paddle_tpu_ok_total", "help")
    with pytest.raises(ValueError):
        reg.counter("paddle_tpu_ok_total", "help", labelnames=("op",))
    with pytest.raises(ValueError):
        reg.counter("paddle_tpu_ok_total", "different help")
    h = reg.histogram("paddle_tpu_ok_seconds", "help", window=64)
    with pytest.raises(ValueError):
        reg.histogram("paddle_tpu_ok_seconds", "help", window=128)
    # read-only access without repeating the declaration
    assert reg.get("paddle_tpu_ok_total") is c
    assert reg.get("paddle_tpu_ok_seconds") is h
    assert reg.get("paddle_tpu_missing") is None


def test_counter_and_labels():
    reg = MetricsRegistry()
    fam = reg.counter("paddle_tpu_rpc_total", "rpcs", ("op",))
    fam.labels(op="get").inc()
    fam.labels(op="get").inc(2)
    fam.labels(op="put").inc()
    assert fam.labels(op="get").value == 3
    assert fam.labels(op="put").value == 1
    with pytest.raises(ValueError):
        fam.labels(method="get")      # wrong label name
    with pytest.raises(ValueError):
        fam.inc()                     # labeled family needs .labels()
    with pytest.raises(ValueError):
        fam.labels(op="get").inc(-1)  # counters are monotonic


def test_histogram_nearest_rank_boundaries():
    """The documented window-boundary contract: empty -> 0.0 for every
    quantile; one sample answers EVERY quantile with itself; no
    interpolation between observations."""
    h = Histogram(window=8)
    assert h.percentile(50) == 0.0 and h.percentile(99) == 0.0
    assert h.snapshot() == {"count": 0, "mean": 0.0, "p50": 0.0,
                            "p90": 0.0, "p99": 0.0}
    h.record(7.5)
    for p in (0, 1, 50, 90, 99, 100):
        assert h.percentile(p) == 7.5
    snap = h.snapshot()
    assert snap["count"] == 1 and snap["p50"] == snap["p99"] == 7.5
    # nearest-rank returns an OBSERVED value, never an interpolation
    h.record(10.0)
    assert h.percentile(50) == 7.5   # rank = ceil(0.5*2) = 1
    assert h.percentile(51) == 10.0  # rank = ceil(0.51*2) = 2
    assert h.percentile(0) == 7.5    # clamped to the minimum


def test_histogram_window_eviction_and_lifetime_totals():
    h = Histogram(window=4)
    for v in range(1, 9):  # 1..8; window keeps 5,6,7,8
        h.record(float(v))
    assert h.count == 8 and h.sum == 36.0   # lifetime, not window
    assert h.percentile(1) == 5.0           # window minimum
    assert h.percentile(100) == 8.0


def test_broken_collector_does_not_poison_scrapes():
    """One raising collector must not 500 the whole exposition: healthy
    families still render and the failure is surfaced as its own
    counter series (per-collector isolation, like /statusz)."""
    reg = MetricsRegistry()
    reg.counter("paddle_tpu_healthy_total", "help").inc(3)

    def broken_collector(r):
        raise RuntimeError("boom")

    reg.register_collector(broken_collector)
    for _ in range(2):  # every scrape isolates, not just the first
        samples, _, _ = parse_exposition(reg.render_prometheus())
    (_, v), = samples["paddle_tpu_healthy_total"]
    assert v == 3
    (labels, errs), = \
        samples["paddle_tpu_observability_collector_errors_total"]
    assert labels["collector"] == "broken_collector" and errs == 2


def test_disabled_registry_is_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("paddle_tpu_x_total", "help")
    c.inc(5)
    assert c.value == 0
    h = reg.histogram("paddle_tpu_h", "help")
    h.record(1.0)
    assert h.percentile(99) == 0.0
    assert reg.names() == []
    assert reg.render_prometheus() == "\n"


def test_default_registry_swap_repoints_executor_metrics(fresh_registry):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [2])
        y = layers.fc(x, size=2)
    exe = pt.Executor()
    exe.run(startup)
    feed = {"x": np.zeros((1, 2), np.float32)}
    exe.run(main, feed=feed, fetch_list=[y])
    exe.run(main, feed=feed, fetch_list=[y])
    fam = fresh_registry.get("paddle_tpu_compile_cache_hits_total")
    assert fam.value >= 1  # second run hit the cache, in THIS registry


# ---------------------------------------------------------------------------
# exposition format
# ---------------------------------------------------------------------------
_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (NaN|[+-]?[0-9eE.+-]+|[+-]Inf)$')
_LABEL_PAIR_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_exposition(text):
    """Strict-enough 0.0.4 parser: every non-comment line must be a
    valid sample; returns (samples {name: [(labels, value)]}, helps,
    types)."""
    samples, helps, types = {}, {}, {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            name, help_ = line[len("# HELP "):].split(" ", 1)
            helps[name] = help_
        elif line.startswith("# TYPE "):
            name, typ = line[len("# TYPE "):].split(" ", 1)
            assert typ in ("counter", "gauge", "summary", "histogram",
                           "untyped"), typ
            types[name] = typ
        else:
            m = _SAMPLE_RE.match(line)
            assert m, f"unparseable exposition line: {line!r}"
            name, labelstr, val = m.groups()
            labels = dict(_LABEL_PAIR_RE.findall(labelstr)) \
                if labelstr else {}
            samples.setdefault(name, []).append((labels, float(val)))
    # every sample belongs to a typed family (allowing _sum/_count)
    for name in samples:
        base = re.sub(r"_(sum|count)$", "", name)
        assert name in types or base in types, \
            f"sample {name} has no # TYPE line"
    return samples, helps, types


def test_render_prometheus_escapes_and_parses():
    reg = MetricsRegistry()
    g = reg.gauge("paddle_tpu_esc", 'help with \\ backslash\nand newline',
                  ("path",))
    g.labels(path='a"b\\c\nd').set(1.5)
    samples, helps, types = parse_exposition(reg.render_prometheus())
    assert types["paddle_tpu_esc"] == "gauge"
    assert "\\n" in helps["paddle_tpu_esc"]
    (labels, value), = samples["paddle_tpu_esc"]
    assert value == 1.5 and labels["path"] == 'a\\"b\\\\c\\nd'


# ---------------------------------------------------------------------------
# telemetry server + the acceptance scrape
# ---------------------------------------------------------------------------
def _get(url, expect_error=None):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        if expect_error is None:
            raise
        return e.code, e.read().decode()


def _build_mlp():
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = 0
    with pt.program_guard(main, startup):
        x = layers.data("x", [8])
        label = layers.data("label", [1])
        pred = layers.fc(x, size=4)
        loss = layers.mean(layers.square(pred - label))
        pt.optimizer.SGDOptimizer(learning_rate=0.05).minimize(loss)
    return main, startup, loss, pred


def _reader(n=6, bs=4):
    def read():
        rng = np.random.RandomState(0)
        for _ in range(n):
            yield {"x": rng.rand(bs, 8).astype(np.float32),
                   "label": rng.rand(bs, 1).astype(np.float32)}
    return read


def test_scrape_running_trainer_and_serving_engine(tmp_path,
                                                   fresh_registry):
    """Acceptance: one GET /metrics during a running trainer + serving
    engine exposes step-time histogram (p99 readable off the summary),
    compile-cache hit/miss counters, retry counters per op,
    circuit-breaker state, and batcher queue depth — in valid
    Prometheus text exposition."""
    from paddle_tpu.resilience import RetryPolicy

    main, startup, loss, pred = _build_mlp()
    trainer = Trainer(loss, main_program=main, startup_program=startup)
    trainer.train(num_passes=2, reader=_reader())

    pt.io.save_inference_model(str(tmp_path), ["x"], [pred], trainer.exe,
                               main_program=main)
    model = serving.load(str(tmp_path))
    engine = model.serve(serving.BatchingConfig(max_batch_size=4,
                                                max_latency_ms=1.0))
    engine.start(warmup=False)
    # a couple of retried ops so per-op retry counters have series
    flaky = {"n": 0}

    def sometimes():
        flaky["n"] += 1
        if flaky["n"] == 1:
            raise ConnectionError("transient")
        return True

    RetryPolicy(max_attempts=3, base_delay_s=0.0).call(
        sometimes, name="obs.flaky")
    try:
        (out,) = engine.predict({"x": np.zeros((2, 8), np.float32)},
                                timeout=30)
        assert out.shape == (2, 4)
        srv = obs.TelemetryServer(port=0, health=engine.health)
        srv.add_status("serving", engine.stats)
        with srv:
            assert srv.port != 0
            code, text = _get(srv.url + "/metrics")
            assert code == 200
            samples, helps, types = parse_exposition(text)

            # step-time histogram with a derivable p99
            assert types["paddle_tpu_train_step_seconds"] == "summary"
            q99 = [v for lab, v in
                   samples["paddle_tpu_train_step_seconds"]
                   if lab.get("quantile") == "0.99"]
            assert len(q99) == 1 and q99[0] > 0
            (_, cnt), = samples["paddle_tpu_train_step_seconds_count"]
            assert cnt == 12  # 2 passes x 6 batches
            (_, steps), = samples["paddle_tpu_train_steps_total"]
            assert steps == 12

            # compile-cache hit/miss counters
            (_, hits), = samples["paddle_tpu_compile_cache_hits_total"]
            (_, misses), = \
                samples["paddle_tpu_compile_cache_misses_total"]
            assert misses >= 1 and hits >= 1

            # retry counters per op
            ops = {lab["op"]: v for lab, v in
                   samples["paddle_tpu_retry_calls_total"]}
            assert ops.get("obs.flaky") == 1
            retries = {lab["op"]: v for lab, v in
                       samples["paddle_tpu_retry_retries_total"]}
            assert retries.get("obs.flaky") == 1

            # circuit-breaker state (engine's breaker, closed)
            states = samples["paddle_tpu_circuit_breaker_state"]
            assert any(v == 0 for _, v in states)

            # batcher queue depth gauge, labeled by engine
            (lab, depth), = \
                samples["paddle_tpu_serving_queue_depth_rows"]
            assert "engine" in lab and depth == 0

            # every family carries help text
            for name in types:
                assert helps.get(name, "").strip(), name

            # healthz 200 while the breaker is closed; statusz carries
            # the engine stats snapshot
            code, body = _get(srv.url + "/healthz")
            assert code == 200 and json.loads(body)["status"] == "ok"
            code, body = _get(srv.url + "/statusz")
            statusz = json.loads(body)
            assert statusz["status"]["serving"]["requests"] == 1
            assert "paddle_tpu_train_steps_total" in statusz["metrics"]
    finally:
        engine.stop()
    # PR 1-3 facade shapes survive the migration
    stats = engine.stats()
    assert stats["requests"] == 1 and "health" in stats
    assert set(stats["latency_s"]) == {"count", "mean", "p50", "p90",
                                       "p99"}


def test_healthz_503_when_breaker_open(fresh_registry):
    from paddle_tpu.resilience import CircuitBreaker, HealthMonitor

    hm = HealthMonitor(CircuitBreaker(failure_threshold=1,
                                      reset_timeout_s=3600))
    hm.record_failure(RuntimeError("boom"))
    with obs.TelemetryServer(port=0, health=hm) as srv:
        code, body = _get(srv.url + "/healthz", expect_error=503)
        assert code == 503
        payload = json.loads(body)
        assert payload["status"] == "unhealthy"
        assert payload["health"]["breaker"]["state"] == "open"
        # unknown path -> 404, not a crash
        code, _ = _get(srv.url + "/nope", expect_error=404)
        assert code == 404


def test_telemetry_server_stop_releases_thread():
    srv = obs.TelemetryServer(port=0).start()
    srv.stop()
    assert not [t for t in threading.enumerate()
                if t.name == "telemetry-server" and t.is_alive()]
    # idempotent
    srv.stop()


# ---------------------------------------------------------------------------
# step tracing
# ---------------------------------------------------------------------------
def test_span_nesting_and_ids():
    assert trace.current() is None
    with trace.step_trace(7) as root:
        assert trace.current() is root
        assert root.parent_id is None and root.name == "step/7"
        with trace.span("feed") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
            assert child.span_id != root.span_id
        assert trace.current() is root
    assert trace.current() is None
    with trace.step_trace(8) as other:
        assert other.trace_id != root.trace_id  # fresh trace per step


def test_profiler_events_carry_trace_args():
    profiler.start_profiler()
    try:
        with trace.step_trace(3) as root:
            with profiler.RecordEvent("pipeline::dispatch",
                                      cat=profiler.CAT_PIPELINE):
                pass
        with profiler.RecordEvent("outside"):
            pass
    finally:
        profiler.stop_profiler()
    evs = {e["name"]: e for e in profiler.events()}
    args = evs["pipeline::dispatch"]["args"]
    assert args["trace_id"] == root.trace_id
    assert args["span_id"] == root.span_id
    # the root span's own event carries its own ids
    assert evs["trace::step/3"]["args"]["span_id"] == root.span_id
    # outside any span: no trace args stamped
    assert "trace_id" not in evs["outside"].get("args", {})


@pytest.mark.chaos
def test_trace_context_propagates_through_rpc_retries():
    """Acceptance (satellite): retry attempts on an injected master.rpc
    fault all carry the SAME trace/span id through jsonrpc — each
    attempt is an rpc::master.rpc profiler event stamped with the
    step's context, and the re-sent request delivers that context to
    the server."""
    from paddle_tpu.distributed.master import Master, MasterClient, \
        MasterServer
    from paddle_tpu.resilience import FaultInjector, RetryPolicy

    ms = MasterServer(Master(), port=0).start()
    client = MasterClient(
        ms.endpoint,
        retry=RetryPolicy(max_attempts=5, base_delay_s=0.0, jitter=0.0))
    profiler.start_profiler()
    try:
        with FaultInjector(seed=3) as fi:
            fi.on("master.rpc", raises=ConnectionError, times=2)
            with trace.step_trace(41) as root:
                client.set_dataset([b"task-1"])
            assert fi.triggered("master.rpc") == 2
        assert client.retries == 2
    finally:
        profiler.stop_profiler()
        client.close()
        ms.shutdown()
    attempts = [e for e in profiler.events()
                if e["name"] == "rpc::master.rpc"]
    assert len(attempts) == 3  # 2 injected drops + 1 success
    for e in attempts:
        assert e["args"]["trace_id"] == root.trace_id
        assert e["args"]["span_id"] == root.span_id
    # the surviving attempt delivered the same context server-side
    assert ms.last_trace == {"trace_id": root.trace_id,
                             "span_id": root.span_id}


# ---------------------------------------------------------------------------
# profiler concurrency (satellite)
# ---------------------------------------------------------------------------
def test_export_chrome_trace_under_concurrent_emission(tmp_path):
    """export snapshots the event list under the profiler lock: every
    export mid-emission must be loadable, internally consistent JSON."""
    profiler.start_profiler()
    stop = threading.Event()

    def emit():
        # paced, not a bare spin: every export dumps ALL events so far,
        # so four unthrottled emitters outran the exporter and the
        # test's time, memory and /tmp use ran away with the machine
        # (100 s alone, the suite's whole 1,470 s limit under -n 6,
        # >10 GB of traces). Emission is still live during every export.
        while not stop.wait(0.0002):
            with profiler.RecordEvent("spin", cat="test"):
                pass

    threads = [threading.Thread(target=emit) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for i in range(25):
            path = tmp_path / f"trace_{i}.json"
            profiler.export_chrome_trace(str(path))
            with open(path) as f:
                data = json.load(f)
            assert all(e["name"] == "spin" for e in data["traceEvents"])
    finally:
        stop.set()
        for t in threads:
            t.join()
        profiler.stop_profiler()
