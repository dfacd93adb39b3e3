"""Driver kind ``serve``: the program's GenerationEngine under an open
loop. Requests are offered when they are DUE, whatever the server is
doing, and every latency is counted from the due time, so a stalled
generator cannot hide queueing; how late the generator ran is reported.

Completion is observed from the client's side: one waiter thread per
request blocks in ``future.result()`` and reads the benchmark's clock
when it returns. ``GenerationFuture`` completes only when a request
retires, so the FIRST token has to be read where the program produces
it: admission is FIFO, one ``generation::prefill[n]`` span (n = the
prompt's length) closes per admitted request, and ``run_prefill``
returns the materialised first token — the k-th prefill span's end is
the k-th accepted request's first token. That inference is checked in
every run and a run in which it fails is not ``correct``: each span's
n must be its request's prompt length, and the completion the spans
imply (a request of N tokens ends with the (N-1)-th decode span after
its prefill) must agree with the observed one to within one decode
step. A program that batches or chunks prefill, or emits several
tokens a step, breaks these and is told so, not measured wrongly.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from .. import weights
from . import resolve, sizes
from ..traffic import serve as traffic

PREFILL, DECODE = "generation::prefill[", "generation::decode_step["
# A request that is not complete this long after the window counts as
# failed and as missing both latencies.
GRACE_S = 30.0
# The configuration stores weights, activations and the KV cache in f32
# and multiplies at the TPU's DEFAULT matmul precision (bf16 passes), as
# its file says; the reference is f32 "highest". With fresh weights the
# top two logits are often closer than that rounding, and either is a
# correct greedy choice. A chosen token whose reference logit is within
# LOGIT_TOL of the reference's maximum passes. Over 3,300 checked tokens
# in eight chip runs the largest gap was 0.0026 (PERF.md, PR 24); 0.01
# is four times that, and a wrong cache row, position or mask moves
# logits by far more (their spread over the vocabulary is ~0.5). The
# tolerance cannot tell f32 storage from bf16, so the storage type is
# checked by itself: every parameter and cache array has the dtype the
# configuration states.
LOGIT_TOL = 0.01
# Requests compared with the reference: evenly spaced over the window's
# completed requests in admission order (the engine fills the lowest
# free slot, so these land in slots all over the array), from a seeded
# offset.
CHECKED_REQUESTS = 32
# Tokens a reference call holds at once: [rows, width, vocab] f32 logits
# of 8192 x 32000 are 1 GB.
REFERENCE_CHUNK_TOKENS = 8192
# How far the observed completion may lie from the one the spans imply,
# beyond one decode step: the waiter thread wakes when the engine's
# thread lets go of the interpreter (5 ms switch interval). A rehearsal
# shares a CPU with the other tests and is given much more.
COMPLETION_SLACK_S = {False: 0.010, True: 0.5}
# At most this share of the completed requests may disagree.
COMPLETION_MISMATCH_SHARE = 0.01


def match_first_tokens(requests: list, spans: list) -> int:
    """FIFO: the k-th ``generation::prefill[n]`` span belongs to the
    k-th request the engine accepted. A shed request (``future`` is
    None) never reaches admission and takes no span. Fills
    ``first_token`` (benchmark clock at span end), ``admitted`` (span
    start) and, from the decode spans that follow, ``implied`` (when
    the spans say the request completed). Returns how many spans do
    not fit their request: a span whose n is not the prompt's length,
    and every span beyond the accepted requests."""
    prefills = [s for s in spans if s.name.startswith(PREFILL)]
    decodes = [s for s in spans if s.name.startswith(DECODE)]
    decode_ends = np.array([s.heard for s in decodes])
    accepted = [r for r in requests if r.future is not None]
    misfits = max(0, len(prefills) - len(accepted))
    for r, s in zip(accepted, prefills):
        if s.name[len(PREFILL):].rstrip("]") != str(len(r.prompt)):
            misfits += 1
            continue
        r.first_token = s.heard
        r.admitted = s.start
        if r.answer_len == 1:
            r.implied = s.heard
            continue
        # decode spans that closed after this prefill, in order
        k = int(np.searchsorted(decode_ends, s.heard, side="right"))
        last = k + r.answer_len - 2
        if last < len(decodes):
            r.implied = float(decode_ends[last])
    return misfits


def _await(r, deadline: float):
    """A client: block until the request's future resolves, and read
    the clock."""
    try:
        r.result = r.future.result(
            timeout=max(0.0, deadline - time.perf_counter()))
        r.completed = time.perf_counter()
    except Exception as e:  # noqa: BLE001 — late or failed
        r.error = type(e).__name__


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def run(ctx) -> dict:
    import paddle_tpu as pt
    from paddle_tpu.serving.generation import (GenerationConfig,
                                               GenerationModel,
                                               GenerationSpec)

    spec_args, tr = sizes(ctx.config, ctx.workload, ctx.rehearse)
    pt.reset_default_programs()
    pt.reset_global_scope()
    pt.amp.enable(ctx.config["precision"] == "amp_bf16")
    spec = GenerationSpec(**dict(spec_args, slots=int(tr["slots"])))
    if tr["prompt_len"]["max"] + tr["answer_len"]["max"] > spec.max_seq_len:
        raise ValueError("the longest prompt and answer do not fit "
                         "max_seq_len: such a request retires early")
    model = GenerationModel.build(spec)
    lm = model.programs["prefill"][spec.prompt_buckets[0]]
    params = [p.name for p in lm.main.all_parameters()]
    weights.reseed(model.scope, lm.main.all_parameters(), ctx.seed)
    ctx.phase("programs built, verified, startup ran")

    # every shape the traffic can use, once, before the engine starts:
    # one prefill program a prompt bucket and one decode program a
    # cache bucket, not the "full" family. Slot 0 takes the junk rows;
    # the first real prefill into it overwrites them and decode masks
    # rows beyond a slot's own position.
    first_calls = []
    for bucket in spec.prompt_buckets:
        t = time.perf_counter()
        model.run_prefill([1] * bucket, 0)
        first_calls.append((t, time.perf_counter()))
    for bucket in spec.cache_buckets:
        t = time.perf_counter()
        model.run_decode(np.ones(spec.slots, np.int64),
                         np.zeros(spec.slots, np.int64), bucket)
        first_calls.append((t, time.perf_counter()))
    misses0 = model.executor.cache_stats["misses"]
    ctx.phase("programs warmed")

    requests = traffic.schedule(tr, ctx.seed, ctx.seconds,
                                spec.vocab_size)
    ramp = float(tr["ramp_s"])
    engine = model.serve(
        config=GenerationConfig(
            max_new_tokens=int(tr["answer_len"]["max"]),
            queue_capacity=int(tr["queue_capacity"])),
        mode="cached").start()
    waiters = []
    try:
        t_ramp = time.perf_counter()
        t0 = t_ramp + ramp
        t1 = t0 + ctx.seconds
        opened = False
        for r in requests:
            due = t_ramp + r.due
            if r.in_window and not opened:
                # the ramp is set-up; the window opens on schedule
                wait = t0 - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                ctx.open_window(at=t0)
                opened = True
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            r.due = due
            r.submitted = time.perf_counter()
            try:
                r.future = engine.submit(r.prompt, r.answer_len)
            except Exception as e:  # noqa: BLE001 — shed or refused
                r.error = type(e).__name__
                continue
            th = threading.Thread(target=_await, args=(r, t1 + GRACE_S),
                                  daemon=True)
            th.start()
            waiters.append(th)
        if not opened:
            ctx.open_window(at=t0)
        wait = t1 - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        ctx.close_window(t1)
        queued_at_end = engine.stats()["queued"]
        for th in waiters:
            th.join()
    finally:
        engine.stop(drain=False, timeout=60)
    stats = engine.stats()
    spans = list(ctx.spans.spans)
    misfits = match_first_tokens(requests, spans)
    steps = [s.dur for s in ctx.spans.named(DECODE, t0, t1)]
    step_s = float(np.median(steps)) if steps else 0.0

    window = [r for r in requests if r.in_window]
    ttft, tpot, tokens, failed = [], [], 0, 0
    wrong_count = disagree = 0
    worst_disagreement = 0.0
    for r in window:
        ok = r.completed is not None and r.first_token is not None
        if ok and len(r.result.tokens) != r.answer_len:
            wrong_count += 1
            ok = False
        if not ok:
            failed += 1
            # missing both latencies: as late as a request can be
            ttft.append(t1 + GRACE_S - r.due)
            tpot.append(GRACE_S)
            continue
        off = abs(r.completed - r.implied) if r.implied is not None \
            else float("inf")
        worst_disagreement = max(worst_disagreement, off)
        if off > step_s + COMPLETION_SLACK_S[ctx.rehearse]:
            disagree += 1
        tokens += len(r.result.tokens)
        ttft.append(r.first_token - r.due)
        tpot.append((r.completed - r.first_token) / (r.answer_len - 1))
    lateness = [r.submitted - r.due for r in window]
    done = len(window) - failed

    compiles_in_window = ctx.spans.compile_count(t0, t1)
    new_misses = model.executor.cache_stats["misses"] - misses0
    cache = [model.scope.get(n) for n in model.cache_names]
    stored = sorted({str(a.dtype) for a in cache}
                    | {str(model.scope.get(n).dtype) for n in params})
    gaps = _check_against_reference(ctx, model, spec, params, window)
    worst_gap = max(gaps) if gaps else float("inf")
    correct = bool(window and wrong_count == 0 and gaps
                   and worst_gap <= LOGIT_TOL
                   and stored == [ctx.config["storage_dtype"]]
                   and misfits == 0
                   and disagree <= COMPLETION_MISMATCH_SHARE * done
                   and compiles_in_window == 0 and new_misses == 0)
    kv_reserved = int(sum(a.nbytes for a in cache))
    del cache
    model.executor.close()

    ctx.run.update(
        kind="serve", requests=window, all_requests=requests,
        t_ramp=t_ramp, first_calls=first_calls, engine_stats=stats,
        kv_reserved_bytes=kv_reserved,
        kv_reserved_positions=spec.slots * spec.max_seq_len)
    third = max(1, len(ttft) // 3)
    ctx.note(check=dict(reference_gap_max=worst_gap, logit_tol=LOGIT_TOL,
                        checked_tokens=len(gaps),
                        wrong_token_counts=wrong_count,
                        stored_as=stored,
                        prefill_spans_that_do_not_fit=misfits,
                        completions_that_disagree=disagree,
                        completion_disagreement_ms_max=
                        worst_disagreement * 1e3,
                        decode_step_ms=step_s * 1e3,
                        compiles_in_window=compiles_in_window,
                        executor_misses_in_window=new_misses),
             offered=dict(rate_per_s=tr["rate_per_s"],
                          in_window=len(window), in_ramp=len(requests)
                          - len(window),
                          prompt_len_mean=float(np.mean(
                              [len(r.prompt) for r in window])),
                          prompt_len_max=max(len(r.prompt)
                                             for r in window),
                          answer_len_mean=float(np.mean(
                              [r.answer_len for r in window])),
                          context_max=max(len(r.prompt) + r.answer_len
                                          for r in window)),
             kv_reserved_bytes=kv_reserved,
             generator_lateness_ms=dict(
                 p50=percentile(lateness, 50) * 1e3,
                 max=max(lateness) * 1e3,
                 max_at_s=window[int(np.argmax(lateness))].due - t0)
             if lateness else None,
             queued_at_window_end=queued_at_end,
             ttft_ms_by_third=[percentile(ttft[k:k + third], 50) * 1e3
                               for k in (0, third, 2 * third)
                               if ttft[k:k + third]],
             first_step_walls=[e - b for b, e in first_calls],
             engine=dict(steps=stats.get("steps"),
                         prefills=stats.get("prefills"),
                         shed=stats.get("shed")))
    return {
        "correct": correct, "attempted": len(window), "failed": failed,
        "end_to_end": {
            "serve_tokens_per_s": tokens / ctx.seconds,
            "ttft_ms_p95": percentile(ttft, 95) * 1e3 if ttft else 0.0,
            "tpot_ms_p95": percentile(tpot, 95) * 1e3 if tpot else 0.0},
        "program_temp_bytes": 0,
    }


def _check_against_reference(ctx, model, spec, params, window) -> list:
    """For CHECKED_REQUESTS completed requests: how far below the
    reference's best logit each generated token sits, position by
    position (0 where the system chose the reference's argmax).
    Requests are grouped by padded width (a power of two) so that the
    reference compiles a handful of shapes, and go through it a few
    rows at a time."""
    done = [r for r in window if r.completed is not None
            and len(r.result.tokens) == r.answer_len]
    if not done:
        return []
    n = min(CHECKED_REQUESTS, len(done))
    stride = len(done) / n
    offset = np.random.default_rng(ctx.seed).uniform(0, stride)
    picks = [done[int(offset + k * stride)] for k in range(n)]
    groups = {}
    for r in picks:
        full = r.prompt + list(r.result.tokens)
        width = max(16, 1 << (len(full) - 1).bit_length())
        groups.setdefault(width, []).append((r, full))
    tape = [np.asarray(model.scope.get(n)) for n in params]
    choice_gap = resolve(ctx.config["reference"]["choice_gap"])
    out = []
    for width, members in sorted(groups.items()):
        rows = max(1, REFERENCE_CHUNK_TOKENS // width)
        rows = min(rows, 1 << (len(members) - 1).bit_length())
        for i in range(0, len(members), rows):
            part = members[i:i + rows]
            tokens = np.ones((rows, width), np.int64)
            for row, (_r, full) in enumerate(part):
                tokens[row, :len(full)] = full
            gap = choice_gap(tape, tokens, spec.n_layer, spec.n_head)
            for row, (r, _full) in enumerate(part):
                p = len(r.prompt)
                # position p-1 predicts the first generated token, ...
                out.extend(gap[row, p - 1:p - 1 + r.answer_len].tolist())
    return out
