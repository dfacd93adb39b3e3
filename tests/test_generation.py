"""Token-serving engine tests (ISSUE 16): greedy bit-identity of the
donated-KV incremental decode against the full re-forward baseline,
continuous-batching admit/retire mid-generation, donation
non-interference with an in-flight training executor, chaos (breaker
trip keeps completed tokens), multi-model hosting + swap, decode cost
rules, and the generation-spec artifact round-trip."""
import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.analysis import cost_model
from paddle_tpu.resilience.faults import FaultInjector
from paddle_tpu.resilience.health import (CircuitBreaker,
                                          CircuitOpenError, HealthMonitor)
from paddle_tpu.serving.generation import (GenerationConfig,
                                           GenerationHost,
                                           GenerationModel,
                                           GenerationSpec, bucket_for)

SPEC_KW = dict(vocab_size=50, max_seq_len=24, slots=2,
               prompt_buckets=(8, 16, 24), cache_buckets=(8, 16, 24),
               n_layer=1, n_head=2, d_model=16, d_inner=32, seed=7,
               eos_id=1)


@pytest.fixture(scope="module")
def model():
    """One compiled model shared by every test in this module (each
    engine run starts from whatever cache state the last one left —
    prefill overwrites a slot's rows, so tests stay independent)."""
    return GenerationModel.build(GenerationSpec(**SPEC_KW))


def _generate_all(model, prompts, mode, max_new_tokens=16):
    eng = model.serve(config=GenerationConfig(max_new_tokens=max_new_tokens),
                      mode=mode).start()
    try:
        futs = [eng.submit(p) for p in prompts]
        return [f.result(timeout=120) for f in futs]
    finally:
        eng.stop(drain=True, timeout=120)


# ---------------------------------------------------------------------------
# bit-identity
# ---------------------------------------------------------------------------
def test_greedy_bit_identity_across_cache_buckets(model):
    """Cached decode must be BIT-identical to full re-forward while the
    generation crosses >= 3 cache buckets (prompt 4 -> length 22 spans
    the 8, 16, and 24 buckets)."""
    prompts = [[5, 9, 3, 2], [7, 3, 2, 4]]
    cached = _generate_all(model, prompts, "cached", max_new_tokens=18)
    reforward = _generate_all(model, prompts, "reforward",
                              max_new_tokens=18)
    for c, r in zip(cached, reforward):
        assert c.tokens == r.tokens
        assert c.finish_reason == r.finish_reason
    # the run really did cross three buckets
    final_len = len(prompts[0]) + len(cached[0].tokens)
    spec = model.spec
    crossed = {bucket_for(n, spec.cache_buckets)
               for n in range(len(prompts[0]) + 1, final_len + 1)}
    assert len(crossed) >= 3, (final_len, crossed)


def test_mid_generation_admit_retire_bit_identity(model):
    """Continuous batching: with 2 slots and 4 requests of different
    lengths, late requests are admitted into slots freed mid-run by
    early retirements — and every request's token stream still equals
    its solo (no batchmates) run."""
    prompts = [[5, 9, 3], [7, 3, 2, 4], [11, 6], [8, 8, 4, 9, 2]]
    budgets = [4, 9, 6, 12]
    eng = model.serve(config=GenerationConfig(max_new_tokens=16)).start()
    try:
        futs = [eng.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)]
        mixed = [f.result(timeout=120) for f in futs]
    finally:
        eng.stop(drain=True, timeout=120)
    # retirements freed slots for the queued requests
    assert eng.metrics.requests.value >= 4
    for prompt, budget, got in zip(prompts, budgets, mixed):
        solo = _generate_all(model, [prompt], "cached",
                             max_new_tokens=budget)[0]
        assert got.tokens == solo.tokens, (prompt, got.tokens,
                                           solo.tokens)
        assert got.finish_reason == solo.finish_reason


def _append_sites():
    from paddle_tpu.observability import default_registry
    fam = default_registry().get("paddle_tpu_kv_append_sites_total")
    return {labels[0]: child.value for labels, child in fam.samples()} \
        if fam is not None else {}


@pytest.mark.parametrize("lane_axis,max_seq_len,buckets", [
    (3, 24, (8, 16, 24)),      # d_key on the lanes: 8-row tiles
    (2, 128, (8, 16, 128)),    # positions on the lanes: 128-lane blocks
], ids=["row_form", "lane_form"])
def test_decode_through_append_kernel_matches_scatter_path(
        monkeypatch, lane_axis, max_seq_len, buckets):
    """The decode programs with kv_cache_append steered to its Pallas
    kernel (interpret mode here; on a TPU the rule picks it by itself)
    emit the token streams of today's scatter path and of the full
    re-forward, and every append site is counted on the path taken."""
    from paddle_tpu.ops import cache_ops
    kw = dict(SPEC_KW, max_seq_len=max_seq_len, prompt_buckets=buckets,
              cache_buckets=buckets)
    prompts = [[5, 9, 3, 2], [7, 3, 2, 4]]

    def streams(mode):
        before = _append_sites()
        model = GenerationModel.build(GenerationSpec(**kw))
        out = _generate_all(model, prompts, mode, max_new_tokens=18)
        after = _append_sites()
        return out, {k: after[k] - before.get(k, 0) for k in after
                     if after[k] != before.get(k, 0)}, model.spec

    scatter, scatter_sites, spec = streams("cached")
    reforward, no_sites, _ = streams("reforward")
    monkeypatch.setattr(cache_ops, "_append_kernel_lane_axis",
                        lambda ctx, cache: lane_axis)
    kernel, kernel_sites, _ = streams("cached")
    for k, s, r in zip(kernel, scatter, reforward):
        assert k.tokens == s.tokens == r.tokens
        assert k.finish_reason == s.finish_reason == r.finish_reason
    # K and V of every layer, once a decode program traced (one a cache
    # bucket the generation entered), all on the one path
    final_len = len(prompts[0]) + len(kernel[0].tokens)
    entered = {bucket_for(n, spec.cache_buckets)
               for n in range(len(prompts[0]) + 1, final_len + 1)}
    sites = 2 * spec.n_layer * len(entered)
    assert len(entered) >= 2
    assert scatter_sites == {"scatter": sites}
    assert kernel_sites == {"kernel": sites}
    assert no_sites == {}


# ---------------------------------------------------------------------------
# donation non-interference
# ---------------------------------------------------------------------------
def test_donated_cache_does_not_disturb_train_executor(model):
    """The decode step donates its KV-cache buffers. Run a training
    loop (its OWN executor/scope, in-flight async dispatches) while the
    generation engine decodes concurrently: the loss trajectory must be
    bit-identical to the serial baseline — donation must never reach
    across executors or corrupt the feed cache."""
    def build_trainer():
        main, startup = pt.Program(), pt.Program()
        main.random_seed = startup.random_seed = 3
        with pt.program_guard(main, startup):
            x = layers.data("x", [6])
            y = layers.data("y", [1])
            pred = layers.fc(x, size=1)
            loss = layers.mean(layers.square(pred - y))
            pt.optimizer.SGDOptimizer(learning_rate=0.05).minimize(loss)
        return main, startup, loss

    def run_train(steps=12):
        main, startup, loss = build_trainer()
        scope = pt.Scope()
        exe = pt.Executor()
        rng = np.random.RandomState(0)
        feeds = [{"x": rng.rand(4, 6).astype(np.float32),
                  "y": rng.rand(4, 1).astype(np.float32)}
                 for _ in range(steps)]
        losses = []
        with pt.scope_guard(scope):
            exe.run(startup)
            results = [exe.run(main, feed=f, fetch_list=[loss.name],
                               sync=False) for f in feeds]
            for r in results:  # materialize after ALL dispatches
                losses.append(float(np.asarray(r.fetches()[0])))
        return losses

    baseline = run_train()

    eng = model.serve(config=GenerationConfig(max_new_tokens=12)).start()
    try:
        futs = [eng.submit([5, 9, 3, 2]), eng.submit([7, 3, 2, 4])]
        concurrent = run_train()  # decode steps interleave with these
        gen = [f.result(timeout=120) for f in futs]
    finally:
        eng.stop(drain=True, timeout=120)
    assert all(len(g.tokens) > 0 for g in gen)
    assert concurrent == baseline


# ---------------------------------------------------------------------------
# chaos: breaker trip never drops completed tokens
# ---------------------------------------------------------------------------
def test_breaker_trip_preserves_completed_tokens(model):
    """Inject a step fault mid-generation with a trip-on-first-failure
    breaker: the in-flight request must resolve with the tokens it
    already completed (finish_reason='aborted'), and the open breaker
    must shed the next submit."""
    solo = _generate_all(model, [[5, 9, 3, 2]], "cached",
                         max_new_tokens=12)[0]
    health = HealthMonitor(breaker=CircuitBreaker(failure_threshold=1,
                                                  reset_timeout_s=3600))
    eng = model.serve(config=GenerationConfig(max_new_tokens=12),
                      health=health).start()
    try:
        with FaultInjector(seed=0) as fi:
            fi.on("generation.step", after=2)  # steps 3+ fail
            res = eng.submit([5, 9, 3, 2]).result(timeout=120)
        assert res.finish_reason == "aborted"
        # prefill token + 2 decode-step tokens survived the trip, and
        # they are the true greedy prefix — nothing invented, nothing
        # dropped
        assert len(res.tokens) == 3
        assert res.tokens == solo.tokens[:3]
        assert eng.health.snapshot()["breaker"]["state"] == "open"
        with pytest.raises(CircuitOpenError):
            eng.submit([1, 2, 3])
        shed = eng.metrics.stats()["shed_by_reason"]
        assert shed.get("circuit_open") == 1, shed
        # result finish_reason is "aborted" (partial stream delivered);
        # the metrics ledger books the CAUSE: a step error
        retired = eng.metrics.stats()["retired_by_reason"]
        assert retired.get("error") == 1, retired
    finally:
        eng.stop(drain=False, timeout=120)


def test_stop_without_drain_keeps_partial_tokens(model):
    """stop(drain=False) mid-generation also resolves in-flight
    requests with their completed tokens instead of dropping them."""
    eng = model.serve(config=GenerationConfig(max_new_tokens=500,
                                              idle_wait_s=0.005)).start()
    fut = eng.submit([5, 9, 3], max_new_tokens=500)
    # wait until at least one token exists, then pull the plug
    deadline = threading.Event()
    for _ in range(2000):
        if eng.metrics.tokens.value >= 1:
            break
        deadline.wait(0.005)
    eng.stop(drain=False, timeout=120)
    res = fut.result(timeout=120)
    assert res.finish_reason == "aborted"
    assert len(res.tokens) >= 1


# ---------------------------------------------------------------------------
# multi-model hosting
# ---------------------------------------------------------------------------
def test_host_routes_budgets_and_swap_preserves_inflight():
    spec_a = GenerationSpec(**SPEC_KW)
    spec_b = GenerationSpec(**{**SPEC_KW, "seed": 11, "vocab_size": 40})
    host = GenerationHost(config=GenerationConfig(max_new_tokens=6),
                          default_budget=4)
    host.deploy("a", spec_a)
    host.deploy("b", spec_b)
    try:
        # both models serve from ONE executor compile cache
        assert host._hosted["a"].model.executor is \
            host._hosted["b"].model.executor
        ra = host.generate("a", [5, 9, 3], timeout=120)
        rb = host.generate("b", [7, 2], timeout=120)
        assert ra.tokens and rb.tokens

        # per-model budget shed leaves the OTHER model serving
        host._hosted["a"].budget = 0
        from paddle_tpu.serving.admission import ServiceOverloadedError
        with pytest.raises(ServiceOverloadedError):
            host.submit("a", [1, 2])
        assert host.generate("b", [7, 2], timeout=120).tokens
        host._hosted["a"].budget = 4

        # swap model a mid-flight: the in-flight request must finish
        # on the old weights (drain), new traffic hits the new model
        old_solo = ra.tokens
        fut = host.submit("a", [5, 9, 3])
        report = host.swap("a", GenerationSpec(**{**SPEC_KW, "seed": 99}),
                           probe_prompts=([3, 4],))
        assert report["outcome"] == "completed", report
        inflight = fut.result(timeout=120)
        assert inflight.tokens == old_solo  # old weights, full stream
        new = host.generate("a", [5, 9, 3], timeout=120)
        assert new.tokens != old_solo  # genuinely the new weights

        # swap rollback: a candidate whose probe fails leaves the old
        # (post-swap) model serving untouched
        bad = GenerationSpec(**{**SPEC_KW, "seed": 5})
        with FaultInjector(seed=0) as fi:
            fi.on("generation.step", times=1000)
            report = host.swap("a", bad, probe_prompts=([3, 4],))
        assert report["outcome"] == "rolled_back", report
        assert host.generate("a", [5, 9, 3], timeout=120).tokens \
            == new.tokens
    finally:
        host.stop(drain=True, timeout=120)


# ---------------------------------------------------------------------------
# cost model: cached-attention decode rules vs hand counts
# ---------------------------------------------------------------------------
def test_decode_cost_hand_counts(model):
    spec = model.spec
    L = spec.cache_buckets[0]  # 8
    lm = model.programs["decode"][L]
    cost = cost_model.program_cost(
        lm.main, feed_shapes={"token_ids": (spec.slots, 1, 1),
                              "positions": (spec.slots,),
                              "lengths": (spec.slots,)})
    slots, h = spec.slots, spec.n_head
    d_key = spec.d_model // spec.n_head
    # SDPA mega-op: q len 1 against the L cached rows, per layer.
    # flops = 4*lead*sq*sk*d + 5*lead*sq*sk with lead=slots*h, sq=1
    sdpa = [c for c in cost.ops
            if c.op_type == "scaled_dot_product_attention"]
    assert len(sdpa) == spec.n_layer
    expect_sdpa = 4 * (slots * h) * 1 * L * d_key + 5 * (slots * h) * 1 * L
    # its bytes: the op is handed the WHOLE [slots, h, max_seq, d]
    # caches and reads at most the bucket's L rows of each (booked at
    # the bound; the slice that carried this read before is gone),
    # plus q, the int64 lengths and the context
    kept = slots * h * L * d_key * 4
    q_bytes = slots * h * 1 * d_key * 4
    expect_bytes = 2 * kept + 2 * q_bytes + slots * 8
    assert L < spec.max_seq_len
    for c in sdpa:
        assert c.exact and c.flops == expect_sdpa, (c.flops, expect_sdpa)
        assert c.bytes_accessed == expect_bytes, (c.bytes_accessed,
                                                  expect_bytes)
    # kv_cache_append: zero flops; bytes = 2 * new rows + index — the
    # whole [slots, h, max_seq, d] cache must NOT be charged per token
    appends = [c for c in cost.ops if c.op_type == "kv_cache_append"]
    assert len(appends) == 2 * spec.n_layer  # k and v per layer
    new_bytes = slots * h * 1 * d_key * 4      # [slots, h, 1, d] f32
    pos_bytes = slots * 8                      # positions int64
    for c in appends:
        assert c.flops == 0
        assert c.bytes_accessed == 2 * new_bytes + pos_bytes, \
            (c.bytes_accessed, 2 * new_bytes + pos_bytes)
    # no slice stands between a cache and its attention any more
    assert not [c for c in cost.ops if c.op_type == "slice"]
    assert cost.unresolved == 0


def test_prefill_cost_write_rows_only(model):
    spec = model.spec
    S = spec.prompt_buckets[0]
    lm = model.programs["prefill"][S]
    cost = cost_model.program_cost(
        lm.main, feed_shapes={"token_ids": (1, S, 1), "lengths": (1,),
                              "slot": (1,)})
    writes = [c for c in cost.ops if c.op_type == "kv_cache_write"]
    assert len(writes) == 2 * spec.n_layer
    d_key = spec.d_model // spec.n_head
    new_bytes = 1 * spec.n_head * S * d_key * 4  # one slot's S rows
    slot_bytes = 8
    for c in writes:
        assert c.flops == 0
        assert c.bytes_accessed == 2 * new_bytes + slot_bytes


# ---------------------------------------------------------------------------
# artifact round-trip
# ---------------------------------------------------------------------------
def test_generation_spec_save_load_roundtrip(tmp_path, model):
    """save -> load must reproduce the decode stream from the SAVED
    weights (not the spec's seed init): mutate a weight first so a
    loader that silently re-randomizes from the seed fails loudly."""
    src = GenerationModel.build(GenerationSpec(**SPEC_KW))
    # perturb one parameter away from its seeded init
    wname = next(n for n in src.scope.local_names()
                 if "lm_head" in n and ".w" in n)
    w = np.asarray(src.scope.find(wname))
    src.scope.set(wname, np.asarray(w) + 0.37)
    before = _generate_all(src, [[5, 9, 3]], "cached", max_new_tokens=8)[0]

    d = str(tmp_path / "gen_model")
    src.save(d, model_version="v7")

    loaded = GenerationModel.load(d)
    assert loaded.version == "v7"
    assert loaded.spec == src.spec
    after = _generate_all(loaded, [[5, 9, 3]], "cached",
                          max_new_tokens=8)[0]
    assert after.tokens == before.tokens
    # the meta itself is readable without rebuilding a model
    from paddle_tpu import io
    scope = pt.Scope()
    with pt.scope_guard(scope):
        _p, _f, _t, meta = io.load_inference_model(
            d, pt.Executor(), return_meta=True)
    gs = meta["generation_spec"]
    assert gs["max_seq_len"] == SPEC_KW["max_seq_len"]
    assert gs["eos_id"] == SPEC_KW["eos_id"]
    assert gs["kv_cache_layout"] == "[slots, n_head, max_seq_len, d_key]"


def test_new_decode_flags_registered():
    from paddle_tpu import flags
    for name in ("PADDLE_TPU_DECODE_SLOTS",
                 "PADDLE_TPU_DECODE_CACHE_BUCKETS",
                 "PADDLE_TPU_DECODE_MODEL_BUDGET"):
        assert name in flags.FLAGS
        assert flags.get(name)


# ---------------------------------------------------------------------------
# spans and each request's life (ISSUE 25)
# ---------------------------------------------------------------------------
def _serve_and_listen(model, prompts, mode, max_new_tokens=6,
                      idle_first=0.0, prepare=None):
    """(futures, engine stats, closed profiler events) of one engine
    run over more prompts than slots. ``idle_first``: seconds the
    started engine is left with nothing to do; ``prepare(engine)`` runs
    before its start."""
    import time
    from paddle_tpu import profiler
    events = []
    profiler.add_event_listener(events.append)
    eng = model.serve(config=GenerationConfig(max_new_tokens=max_new_tokens),
                      mode=mode)
    if prepare is not None:
        prepare(eng)
    eng.start()
    try:
        time.sleep(idle_first)
        futs = [eng.submit(p) for p in prompts]
        for f in futs:
            f.result(timeout=120)
    finally:
        eng.stop(drain=True, timeout=120)
        profiler.remove_event_listener(events.append)
    return futs, eng.stats(), events


def _driver_spans(events):
    """(the loop thread's events, named(prefix), inside(child, parent))"""
    driver = [e for e in events
              if e["args"]["thread"] == "generation-driver"]

    def named(prefix):
        return [e for e in driver if e["name"].startswith(prefix)]

    def inside(child, parent):
        return parent["ts"] <= child["ts"] and child["ts"] + \
            child["dur"] <= parent["ts"] + parent["dur"]

    return driver, named, inside


PROMPTS = [[3, 4, 5], [6, 7], [8, 9, 10, 11], [12], [13, 14, 15]]


@pytest.mark.parametrize("mode", ["cached", "reforward"])
def test_each_request_carries_its_four_timestamps(model, mode):
    import time
    t_before = time.perf_counter()
    futs, stats, _events = _serve_and_listen(model, PROMPTS, mode)
    t_after = time.perf_counter()
    for f in futs:
        life = [f.enqueued_at, f.admitted_at, f.first_token_at,
                f.completed_at]
        assert None not in life
        assert life == sorted(life)
        assert t_before <= life[0] and life[-1] <= t_after
    # two slots, five requests: some waited for a slot behind others
    waits = sorted(f.admitted_at - f.enqueued_at for f in futs)
    assert waits[-1] > waits[0]
    # FIFO admission: admitted in the order they were enqueued
    order = sorted(futs, key=lambda f: f.enqueued_at)
    assert [f.admitted_at for f in order] == \
        sorted(f.admitted_at for f in futs)
    # both histograms took one sample a retired request
    for key in ("queue_wait_seconds", "ttft_seconds"):
        assert stats[key]["count"] == len(futs), stats[key]
    assert stats["ttft_seconds"]["mean"] >= \
        stats["queue_wait_seconds"]["mean"] > 0


def test_a_request_failed_in_the_queue_has_no_slot_timestamps(model):
    from paddle_tpu.serving.batcher import ServingStopped
    eng = model.serve(config=GenerationConfig(max_new_tokens=16),
                      mode="cached").start()
    futs = [eng.submit([3 + i, 4]) for i in range(8)]
    eng.stop(drain=False, timeout=120)
    dropped = []
    for f in futs:
        try:
            f.result(timeout=30)
        except ServingStopped:
            dropped.append(f)
    assert dropped, "eight requests on two slots: some never got one"
    for f in dropped:
        assert f.admitted_at is None and f.first_token_at is None
        assert f.enqueued_at <= f.completed_at
    stats = eng.stats()
    assert stats["queue_wait_seconds"]["count"] == \
        len(futs) - len(dropped)


@pytest.mark.parametrize("mode, step", [
    ("cached", "generation::decode_step["),
    ("reforward", "generation::reforward_step[")])
def test_iteration_span_and_its_children(model, mode, step):
    futs, stats, events = _serve_and_listen(model, PROMPTS, mode)
    driver, named, inside = _driver_spans(events)
    iterations, steps = named("generation::iteration"), named(step)
    prefills = named("generation::prefill[")
    # the counts the benchmark's driver holds the engine to
    assert len(steps) == stats["steps"]
    assert len(prefills) == stats["prefills"] == \
        (len(PROMPTS) if mode == "cached" else 0)
    assert sorted(e["name"] for e in prefills) == sorted(
        f"generation::prefill[{len(p)}]" for p in PROMPTS)[:len(prefills)]
    # one iteration a step: each step in exactly one iteration, no
    # iteration with two, and every iteration admitted or stepped
    for it in iterations:
        assert len([s for s in steps if inside(s, it)]) <= 1
        assert [e for e in steps + prefills + named(
            "generation::deliver") if inside(e, it)]
    admits, retires = named("generation::admit"), \
        named("generation::retire")
    telemetry = named("generation::telemetry")
    for child in steps + prefills + named("generation::build_step") + \
            named("generation::deliver") + telemetry + admits + retires:
        assert len([it for it in iterations if inside(child, it)]) == 1
    assert len(named("generation::build_step")) == len(steps)
    # a deliver after every step, and in cached mode after every prefill
    assert len(named("generation::deliver")) == len(steps) + len(prefills)
    # ONE telemetry a step and one a prefill: a steady decode pass (no
    # admission in it) holds exactly one
    assert len(telemetry) == len(steps) + len(prefills)
    for it in iterations:
        if not [a for a in admits if inside(a, it)]:
            assert len([t for t in telemetry if inside(t, it)]) == 1
    # an admit span only on a pass that had requests to admit, at most
    # one a pass, and every prefill (with its telemetry and deliver)
    # inside one; five requests on two slots: some were requeued, so
    # there are more admits than it takes to admit each once
    for it in iterations:
        assert len([a for a in admits if inside(a, it)]) <= 1
    for p in prefills:
        assert len([a for a in admits if inside(p, a)]) == 1
    assert admits and len(admits) <= len(iterations)
    if mode == "cached":
        # the first prefill of a pass starts where the admit does, give
        # or take the slot scan: an admit without pending work is none
        assert all(a["dur"] > 0 for a in admits)
    # one retire a retired request, each inside a deliver (the token
    # that finished it)
    assert len(retires) == len(futs) == \
        sum(stats["retired_by_reason"].values())
    for r in retires:
        assert len([d for d in named("generation::deliver")
                    if inside(r, d)]) == 1
    # the executor's spans fall inside the step spans by themselves
    for s in steps + prefills:
        kinds = {e["name"] for e in named("pipeline::") if inside(e, s)}
        assert {"pipeline::prepare", "pipeline::dispatch",
                "pipeline::commit", "pipeline::fetch_sync"} <= kinds
    assert {e["tid"] for e in driver} == {driver[0]["tid"]}
    assert all(e["tid"] != threading.get_ident() for e in driver)


# ---------------------------------------------------------------------------
# the loop accounts for every instant of a pass (ISSUE 55)
# ---------------------------------------------------------------------------
TOP_LEVEL = ("generation::idle_wait", "generation::collect",
             "generation::iteration")


@pytest.mark.parametrize("mode", ["cached", "reforward"])
def test_three_spans_tile_the_loop_threads_timeline(model, mode):
    _futs, _stats, events = _serve_and_listen(model, PROMPTS, mode,
                                              idle_first=0.03)
    driver, named, inside = _driver_spans(events)
    top = sorted((e for e in driver if e["name"] in TOP_LEVEL),
                 key=lambda e: e["ts"])
    assert {e["name"] for e in top} == set(TOP_LEVEL)
    # no two overlap (a microsecond clock's rounding aside) ...
    for a, b in zip(top, top[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 0.5, (a, b)
    # ... and together they cover the thread's life
    life = top[-1]["ts"] + top[-1]["dur"] - top[0]["ts"]
    assert sum(e["dur"] for e in top) >= 0.98 * life
    # every other span of the thread lies inside one of the three
    for e in driver:
        if e["name"] not in TOP_LEVEL + ("generation::stall",
                                         "runtime::gc"):
            assert len([t for t in top if inside(e, t)]) == 1, e
    # a collect before every iteration; the engine idled before the
    # first request and after the last: one span a stretch of waiting,
    # not one a 5-ms timeout
    for it in named("generation::iteration"):
        before = [t for t in top if t["ts"] < it["ts"]]
        assert before and before[-1]["name"] == "generation::collect"
    waits = named("generation::idle_wait")
    assert 1 <= len(waits) <= 3
    assert max(w["dur"] for w in waits) >= 0.02 * 1e6


@pytest.mark.parametrize("mode", ["cached", "reforward"])
def test_the_loop_counters_are_the_passes_own(model, mode):
    _futs, stats, events = _serve_and_listen(model, PROMPTS, mode,
                                             idle_first=0.03)
    driver, named, inside = _driver_spans(events)
    loop = stats["loop"]
    assert set(loop) == {"passes", "wall_seconds", "cpu_seconds",
                         "device_wait_seconds", "voluntary_switches",
                         "involuntary_switches"}
    iterations = named("generation::iteration")
    # a pass that only waited counts nowhere
    assert loop["passes"] == len(iterations)
    # a thread cannot run longer than the wall clock says; the two are
    # different clocks of the kernel's (the monotonic one is slewed, the
    # scheduler's is not) and agree to a part in ten thousand
    # the CPU seconds hold what the thread burns INSIDE a wait too (at
    # toy size a fetch is mostly the conversion of what was fetched)
    assert 0 < loop["cpu_seconds"] <= (
        loop["wall_seconds"] + loop["device_wait_seconds"]) * 1.001 + 1e-4
    # the waits taken out of the wall are the thread's fetch_sync spans
    fetch = named("pipeline::fetch_sync")
    assert loop["device_wait_seconds"] == pytest.approx(
        sum(e["dur"] for e in fetch) * 1e-6, rel=1e-6)
    # wall + device wait = the passes as the spans tile them: collect
    # and iteration, none of the idle wait (30 ms of it at least)
    tiled = sum(e["dur"] for e in iterations
                + named("generation::collect")) * 1e-6
    total = loop["wall_seconds"] + loop["device_wait_seconds"]
    assert abs(total - tiled) <= 0.05 * tiled + 1e-3, (total, tiled)
    assert loop["voluntary_switches"] >= 0
    assert loop["involuntary_switches"] >= 0
    # and on /metrics, under the engine's label
    from paddle_tpu.observability import default_registry
    assert default_registry().get(
        "paddle_tpu_decode_loop_passes_total") is not None


def test_a_pass_that_did_not_run_is_a_stall_span_with_its_evidence(
        model, tmp_path):
    """A sleep inside a pass (the generation.step fault point's delay
    mode): the loop's thread neither ran nor waited for the device."""
    from paddle_tpu.observability.flight_recorder import FlightRecorder
    rec = FlightRecorder(dump_dir=str(tmp_path), min_interval_s=0).enable()
    try:
        with FaultInjector(seed=0) as fi:
            fi.on("generation.step", delay_s=0.08, times=1, after=2)
            _futs, stats, events = _serve_and_listen(model, PROMPTS,
                                                     "cached")
    finally:
        rec.disable()
    stalls = [e for e in events if e["name"] == "generation::stall"
              and e["args"]["wall"] >= 0.075]
    assert len(stalls) == 1, stalls
    stall = stalls[0]
    assert stall["args"]["thread"] == "generation-driver"
    args = stall["args"]
    assert set(args) >= {"passes", "wall", "cpu", "device_wait",
                         "voluntary", "involuntary", "process_cpu"}
    # the stretch of passes the clocks were read over: the one that
    # slept and at most 50 ms of its neighbours, which ran
    assert args["passes"] >= 1
    assert args["cpu"] < 0.06 and args["wall"] - args["cpu"] > 0.05
    assert args["voluntary"] >= 1       # the sleep blocked
    # over the stretch's interval: it holds the iteration that slept
    _driver, named, inside = _driver_spans(events)
    held = [it for it in named("generation::iteration")
            if inside(it, stall)]
    assert len(held) == args["passes"]
    assert len([it for it in held if it["dur"] >= 0.08 * 1e6]) == 1
    # the flight recorder's ring holds the same record
    assert stall in rec.events()
    assert not rec.dumps()              # a stall is no failure
    assert stats["loop"]["wall_seconds"] - stats["loop"]["cpu_seconds"] \
        > 0.05


def test_a_pass_that_ran_all_its_time_is_no_stall(model):
    """A busy loop of the same length on the loop's thread: slow Python,
    not a thread that does not run."""
    import time
    burned = []

    def prepare(eng):
        step = eng._step

        def busy_step():
            if len(burned) == 2:
                until = time.thread_time() + 0.08
                while time.thread_time() < until:
                    pass
            burned.append(1)
            step()
        eng._step = busy_step

    _futs, stats, events = _serve_and_listen(model, PROMPTS, "cached",
                                             prepare=prepare)
    assert len(burned) > 2
    stalls = [e for e in events if e["name"] == "generation::stall"]
    # none; on a machine that took the core away meanwhile, the span
    # says so itself
    assert all(e["args"]["involuntary"] > 0 for e in stalls), stalls
    assert stats["loop"]["cpu_seconds"] >= 0.08
