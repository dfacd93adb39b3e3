"""Metric-name lint: after a smoke train + serve run, every family in
the process-wide registry must match the paddle_tpu_* naming contract
and carry help text. This is the drift guard for later PRs — a producer
that invents an off-namespace or undocumented metric fails here, not in
some dashboard six PRs later."""
import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers, serving
from paddle_tpu.observability import default_registry
from paddle_tpu.observability.registry import METRIC_NAME_RE
from paddle_tpu.trainer import Trainer


def _smoke_train_and_serve(tmp_path):
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = 0
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        label = layers.data("label", [1])
        pred = layers.fc(x, size=2)
        loss = layers.mean(layers.square(pred - label))
        pt.optimizer.SGDOptimizer(learning_rate=0.05).minimize(loss)
    trainer = Trainer(loss, main_program=main, startup_program=startup)

    def reader():
        rng = np.random.RandomState(1)
        for _ in range(3):
            yield {"x": rng.rand(2, 4).astype(np.float32),
                   "label": rng.rand(2, 1).astype(np.float32)}

    trainer.train(num_passes=1, reader=reader)
    pt.io.save_inference_model(str(tmp_path), ["x"], [pred], trainer.exe,
                               main_program=main, model_version="v1")
    model = serving.load(str(tmp_path))
    engine = model.serve(serving.BatchingConfig(max_batch_size=2,
                                                max_latency_ms=1.0))
    engine.start(warmup=False)
    try:
        engine.predict({"x": np.zeros((1, 4), np.float32)}, timeout=30)
    finally:
        engine.stop()
    # ISSUE 7 lifecycle families: a hot-swap through a ModelHost (with
    # admission control attached) populates swap/version/canary/shed
    host = serving.ModelHost(
        str(tmp_path),
        config=serving.BatchingConfig(max_batch_size=2,
                                      batch_buckets=[2],
                                      max_latency_ms=1.0),
        admission=serving.AdmissionConfig(max_queue_rows=64),
        warmup=False).start()
    try:
        host.predict({"x": np.zeros((1, 4), np.float32)}, timeout=30)
        report = host.swap(str(tmp_path), canary_fraction=0.0,
                           version="v2")
        assert report["outcome"] == "completed"
    finally:
        host.stop(timeout=120)
    _smoke_generation()
    _smoke_embedding()
    return host.host_label


def _smoke_generation():
    """Populate the token-serving families (ISSUE 16): one tiny
    GenerationHost deploy + a shed, so paddle_tpu_decode_* and the host
    routing families all carry samples."""
    from paddle_tpu.serving.admission import ServiceOverloadedError
    from paddle_tpu.serving.generation import (GenerationConfig,
                                               GenerationHost,
                                               GenerationSpec)
    spec = GenerationSpec(vocab_size=32, max_seq_len=8, slots=1,
                          prompt_buckets=(8,), cache_buckets=(8,),
                          n_layer=1, n_head=2, d_model=8, d_inner=16,
                          seed=0, eos_id=0)
    host = GenerationHost(config=GenerationConfig(max_new_tokens=2),
                          default_budget=1)
    host.deploy("gm", spec)
    try:
        host.generate("gm", [3, 4], timeout=60)
        # drive one model_budget shed through the real admission path
        host._hosted["gm"].budget = 0
        try:
            host.submit("gm", [5])
        except ServiceOverloadedError:
            pass
        else:
            raise AssertionError("budget=0 submit was not shed")
    finally:
        host.stop(timeout=120)


def _smoke_embedding():
    """Populate the sharded-embedding families (ISSUE 19): a few
    hot-cached ShardedTable steps, forcing one cache refresh so every
    paddle_tpu_embed_* family carries samples."""
    from paddle_tpu.embedding import ShardedTable, TableConfig
    table = ShardedTable(TableConfig("metrics_smoke", vocab=64, dim=4,
                                     optimizer="adagrad", lr=0.1),
                         mesh=None, hot_cache=True)
    table.hot_cache.refresh_interval = 1   # refresh on the first apply
    rng = np.random.RandomState(0)
    for _ in range(2):
        ids = rng.randint(0, 64, size=(8,))
        table.apply_gradients(
            ids, rng.rand(8, 4).astype(np.float32))
        table.lookup(ids)


def test_registry_names_and_help_after_smoke_run(tmp_path, monkeypatch):
    # the CPU backend is in no peak table: name the peak the
    # paddle_tpu_mfu family divides by, or it is (rightly) absent
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "197e12")
    host_label = _smoke_train_and_serve(tmp_path)
    reg = default_registry()
    # families() runs the collectors, so pull-model producers (retry
    # counters, breaker state) materialize their families too
    fams = reg.families()
    # the smoke run must actually have populated the registry
    names = {f.name for f in fams}
    for expected in ("paddle_tpu_train_steps_total",
                     "paddle_tpu_train_step_seconds",
                     "paddle_tpu_compile_cache_misses_total",
                     "paddle_tpu_serving_requests_total",
                     "paddle_tpu_circuit_breaker_state",
                     # ISSUE 6: always-on attribution families
                     "paddle_tpu_mfu",
                     "paddle_tpu_model_flops",
                     "paddle_tpu_step_phase_seconds",
                     # ISSUE 7: serving lifecycle families
                     "paddle_tpu_serving_swaps_total",
                     "paddle_tpu_serving_shed_total",
                     "paddle_tpu_serving_model_version",
                     "paddle_tpu_serving_canary_requests_total",
                     # ISSUE 16: token-serving families
                     "paddle_tpu_decode_requests_total",
                     "paddle_tpu_decode_tokens_total",
                     "paddle_tpu_decode_steps_total",
                     "paddle_tpu_decode_prefills_total",
                     "paddle_tpu_decode_retired_total",
                     "paddle_tpu_decode_shed_total",
                     "paddle_tpu_decode_step_seconds",
                     "paddle_tpu_decode_prefill_seconds",
                     "paddle_tpu_decode_slots_active",
                     "paddle_tpu_decode_slots_total",
                     "paddle_tpu_decode_host_requests_total",
                     "paddle_tpu_decode_host_swaps_total",
                     "paddle_tpu_decode_host_models",
                     # ISSUE 19: sharded-embedding families
                     "paddle_tpu_embed_lookups_total",
                     "paddle_tpu_embed_ids_total",
                     "paddle_tpu_embed_hot_cache_hits_total",
                     "paddle_tpu_embed_hot_cache_misses_total",
                     "paddle_tpu_embed_hot_cache_hit_ratio",
                     "paddle_tpu_embed_touched_rows",
                     "paddle_tpu_embed_applies_total",
                     "paddle_tpu_embed_cache_refreshes_total",
                     "paddle_tpu_embed_cache_staleness_steps",
                     "paddle_tpu_embed_table_rows",
                     # ISSUE 20: memory-planner family
                     "paddle_tpu_memory_peak_bytes",
                     # ISSUE 25: what a compile cost, each request's
                     # wait for a slot and time to first token
                     "paddle_tpu_compile_phase_seconds_total",
                     "paddle_tpu_persistent_cache_hits_total",
                     "paddle_tpu_persistent_cache_misses_total",
                     "paddle_tpu_decode_queue_wait_seconds",
                     "paddle_tpu_decode_ttft_seconds",
                     # ISSUE 55: the driver loop's own account of its
                     # passes, from its thread clock
                     "paddle_tpu_decode_loop_passes_total",
                     "paddle_tpu_decode_loop_wall_seconds_total",
                     "paddle_tpu_decode_loop_cpu_seconds_total",
                     "paddle_tpu_decode_loop_device_wait_seconds_total",
                     "paddle_tpu_decode_loop_voluntary_switches_total",
                     "paddle_tpu_decode_loop_involuntary_switches_total"):
        assert expected in names, f"smoke run did not publish {expected}"
    # the generation smoke shed exactly through the host budget path
    gen_shed = {key for key, _ in
                reg.get("paddle_tpu_decode_shed_total").samples()}
    assert any(k[1] == "model_budget" for k in gen_shed), gen_shed
    # the hot-swap left exactly one live version series (v2=1, v1=0)
    # for THIS host — other tests' hosts share the global registry, so
    # scope by the host label instead of asserting across the process
    ver = {key: g.value for key, g in
           reg.get("paddle_tpu_serving_model_version").samples()
           if key[0] == host_label}
    assert sum(v == 1.0 for v in ver.values()) == 1, ver
    assert ver.get((host_label, "v2")) == 1.0, ver
    swaps = {key: c.value for key, c in
             reg.get("paddle_tpu_serving_swaps_total").samples()}
    assert any(key[1] == "completed" and v >= 1
               for key, v in swaps.items()), swaps
    # the attribution families carry both producers: the trainer's
    # job="train" series and the engine's job="engine_<n>" series
    mfu_jobs = {key[0] for key, _ in reg.get("paddle_tpu_mfu").samples()}
    assert "train" in mfu_jobs
    assert any(j.startswith("engine_") for j in mfu_jobs), mfu_jobs
    for fam in fams:
        assert METRIC_NAME_RE.match(fam.name), (
            f"metric {fam.name!r} violates the naming contract "
            f"{METRIC_NAME_RE.pattern!r}")
        assert fam.help and fam.help.strip(), \
            f"metric {fam.name!r} has no help text"
        assert fam.exposition_type in ("counter", "gauge", "summary")


def test_registry_rejects_offnamespace_names():
    reg = default_registry()
    for bad in ("serving_requests_total", "paddle_tpu_Bad",
                "paddle_tpu_", "paddle_tpu_bad-name"):
        try:
            reg.counter(bad, "help")
        except ValueError:
            continue
        raise AssertionError(f"registry accepted bad name {bad!r}")
