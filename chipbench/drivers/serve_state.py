"""Driver kind ``serve_state``: a token server whose slots own more than
a KV cache — the ``hybrid_ssm`` family's recurrent states and
convolution windows beside the attention layers' keys and values —
under the same open loop as kind ``serve`` (drivers/serve.py, whose
schedule, first-token inference, waiters and percentile this file
IMPORTS and does not copy).

What differs from ``serve``:

* the spec names its family, and its architecture is the
  configuration's own published keys (top level of the file), not a
  second copy under ``builder``;
* storage is checked array by array against the configuration's table
  ``storage_dtypes`` (weights, KV and convolution state bfloat16; the
  recurrent state, norm scales, A_log, dt_bias and D float32), where
  ``serve`` wants one dtype for all;
* the comparison with the reference has limits of its own (below):
  ``serve``'s 0.01 is argued for float32 storage and another logit
  scale; the slots' state is read too, each kind against the
  reference's, the slow heads of the recurrent state after 600 decode
  steps among them: the one reading that tells a float32 state from a
  rounded one;
* time to first token is measured and printed in the notes
  (``ttft_ms``) and is NOT among the cell's end-to-end metrics: while
  prefill is serial inside admission its 95th percentile is a queue's
  (PERF.md section 7).
"""
from __future__ import annotations

import threading
import time

import numpy as np

from .. import weights
from . import resolve, sizes
from ..traffic import serve as traffic
from .serve import (CHECKED_REQUESTS, COMPLETION_MISMATCH_SHARE,
                    COMPLETION_SLACK_S, DECODE, _await,
                    match_first_tokens, percentile)

# A request that is not complete this long after the window counts as
# failed and as missing both latencies. ``serve`` waits 30 s for replies
# of at most 256 tokens; here the longest reply is 1024 tokens of 29-34
# ms, 30-35 s by itself, and one due at the window's end was 0.6 s late
# for 30 s in one traced run (PR 49). The wait costs nothing where every
# reply is in: the waiters return when their requests do.
GRACE_S = 45.0

# The comparison: for CHECKED_REQUESTS completed requests every
# generated token's REFERENCE logit is set against the reference's best
# at that position (0 where the system chose the reference's argmax).
# bfloat16 weights are exact in the float32 reference; what differs is
# bfloat16 activations and products, which move a logit by ~1e-4 of a
# vocabulary whose logits spread 0.017 (embedding_std 0.003 x
# sqrt(2048) / 8): where the reference's two best are closer than that
# either is a correct greedy choice, and about 7 tokens in 100 are not
# the reference's own. Two limits, each between what the change read
# over its seeds and what the controls read that the tokens can see
# (PERF.md section 6, my chip runs, PR 49): the WORST gap — the change
# 0.0015-0.0025 over 12 runs; the convolution's window shifted by one
# 0.108, pad rows advancing the state 0.048 — and the MEAN gap over the
# checked tokens — the change 2.6e-5 to 3.8e-5; the two controls 1.24e-3
# and 1.07e-3 — which a fault that moves every logit a little raises
# long before it moves the worst.
GAP_MAX_TOL = 0.008
GAP_MEAN_TOL = 1.5e-4
# The tokens cannot see every fault: with fresh weights a mixer's output
# is mostly its skip term D x and the convolution, the recurrent state
# carries a few per cent of it, and ONE layer's state left at what its
# prefill wrote moves the logits no more than bfloat16 rounding does
# (mean gap 3.56e-5 beside 3.08e-5 on the same requests). So the state
# is also read directly. After the window PROBE_ROWS seeded token rows a
# shape go through the programs into slots of their own — a prefill of
# the first tokens (100 of a 128 bucket: 28 pad rows; 300: past a chunk
# and into the flash kernel's bucket), then the rest one decode step a
# token — and each kind of state those slots hold is set against what
# the reference keeps of the same rows, a layer at a time: the largest
# ||system - reference|| / ||reference|| over rows and layers, by kind.
# The change reads 0.026 (ssm), 0.029 (conv), 0.027 (kv): bfloat16
# activations, 40 layers deep. One layer's update skipped reads 0.61
# (ssm); the window shifted 0.19 (ssm) and 0.28 (kv); pad rows advancing
# the state 1.36 (ssm) and 0.41 (conv).
#
# The WIDTH of the recurrent state is a fourth thing those three cannot
# see: a state rounded to bfloat16 every step reads 0.028 here and
# 2.9e-5 above, as float32 does (0.024 and 2.9e-5), because a head that
# forgets its state within a few steps forgets the rounding with it and
# the activations' drift covers the rest. It shows where the
# configuration says it does (assumed.state_dtype): on the SLOW heads,
# which add a thousandth of their state's size a step and keep every
# rounding. So the second shape decodes 600 steps, and ``ssm_slow`` is
# the same relative error over the heads alone whose nominal forgetting
# a step, softplus(dt_bias) exp(A_log), is at most SLOW_RATE (the
# reference's ``rates``: 36 of the 2,304), pooled over the rows and
# layers of a shape: their state is a sum over hundreds of steps, in
# which the activations' drift averages out (0.0029 after 600 steps,
# 0.0056 after 28) and the roundings add up — a state stored bfloat16,
# and a float32 array rounded in the kernel every step, both read 0.021
# after 150 steps, 0.032 after 300 and 0.052 after 600 (my chip runs,
# PR 49: PERF.md section 6). The limit stands between the larger of the
# change's two readings and 0.052. Beside it ``bf16_share``: the share
# of the probed recurrent state's non-zero values that carry nothing
# below bfloat16's 8 bits (3.0e-5 of the change's, all of both
# controls') — a state that is rounded where it is STORED, whatever the
# array's dtype says.
PROBES = ((100, 28), (300, 600))     # (tokens prefilled, tokens decoded)
REHEARSAL_PROBES = ((5, 3), (11, 20))
PROBE_ROWS = 4
SLOW_RATE = 0.003
STATE_TOL = {"ssm": 0.08, "conv": 0.08, "kv": 0.08, "ssm_slow": 0.013}
BF16_SHARE_TOL = 0.01
# Completions: ``serve``'s share (1 % of the completed requests may lie
# further from the spans' completion than a decode step and the slack),
# but never under ONE request: a traced window holds 26 requests, 1 % of
# which is none, and a waiter that wakes behind a 50-ms prefill's
# dispatch under the profiler (53 ms read once, PR 49) is no fault of
# the first-token inference, which would disagree on most requests.
# positions a reference batch holds at once (rows x width)
REFERENCE_CHUNK_TOKENS = 8192
# parameters a call of weights.reseed draws anew
RESEED_ARRAYS = 16


def build_spec(config: dict, spec_args: dict, slots: int, rehearse: bool):
    """The GenerationSpec of a configuration file whose top level holds
    the published keys: ``arch`` is read from there (a rehearsal's toy
    sizes over it), storage from ``storage_dtypes``."""
    from paddle_tpu.models.hybrid_ssm import ARCH_KEYS
    from paddle_tpu.serving.generation import GenerationSpec
    arch = {k: config[k] for k in ARCH_KEYS}
    if rehearse:
        arch.update(config["rehearse"]["arch"])
    family = dict(arch=arch, dtypes=dict(config["storage_dtypes"]),
                  embedding_std=config["assumed_values"]["embedding_std"])
    return GenerationSpec(**dict(spec_args, slots=slots,
                                 family=config["builder"]["family"],
                                 arch=family))


def storage_faults(model, table: dict) -> list:
    """Every stored array whose dtype is not what the configuration's
    table gives its kind: per-slot state by kind; of the parameters the
    1-D float32 ones are ``scales`` (norm scales, A_log, dt_bias, D;
    the convolution's bias is 1-D at the weights' width) and the rest
    ``weights``."""
    faults = []
    for kind, names in model.state_kinds.items():
        for n in names:
            have = str(model.scope.get(n).dtype)
            if have != table[kind]:
                faults.append((n, have, table[kind]))
    lm = model.programs["prefill"][model.spec.prompt_buckets[0]]
    for p in lm.main.all_parameters():
        have = str(model.scope.get(p.name).dtype)
        if have not in (table["weights"], table["scales"]) or (
                len(p.shape) == 2 and have != table["weights"]):
            faults.append((p.name, have, "weights or scales"))
    return faults


def run(ctx) -> dict:
    import paddle_tpu as pt
    from paddle_tpu.serving.generation import (GenerationConfig,
                                               GenerationModel)

    spec_args, tr = sizes(ctx.config, ctx.workload, ctx.rehearse)
    pt.reset_default_programs()
    pt.reset_global_scope()
    pt.amp.enable(False)
    spec = build_spec(ctx.config, spec_args, int(tr["slots"]),
                      ctx.rehearse)
    if tr["prompt_len"]["max"] + tr["answer_len"]["max"] > spec.max_seq_len:
        raise ValueError("the longest prompt and answer do not fit "
                         "max_seq_len: such a request retires early")
    peaks = []                    # (after what, the allocator's peak)

    def peak(what):
        st = ctx.devices[0].memory_stats() or {}
        peaks.append((what, int(st.get("peak_bytes_in_use") or 0)))

    model = GenerationModel.build(spec)
    peak("startup")
    lm = model.programs["prefill"][spec.prompt_buckets[0]]
    every = lm.main.all_parameters()
    params = [p.name for p in every]
    # a few arrays a call: one call over all 3.2 G weights holds their
    # random bits (4 bytes a weight) beside the slots' state
    for i in range(0, len(every), RESEED_ARRAYS):
        weights.reseed(model.scope, every[i:i + RESEED_ARRAYS],
                       ctx.seed + i)
    peak("weights drawn")
    ctx.phase("programs built, verified, startup ran")

    # every shape the traffic can use, once, before the engine starts
    # (slot 0 takes the junk; a real prefill overwrites all of a slot)
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
    peak("window")
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
    faults = storage_faults(model, ctx.config["storage_dtypes"])
    reserved = model.state_bytes()
    tape = [np.asarray(model.scope.get(n)) for n in params]
    t_probe = time.perf_counter()
    probes = run_probes(ctx, model, spec)
    rounded = bf16_share(probes, model.state_kinds["ssm"])
    # the slots' state is done with: its 7 GB make room for the
    # reference's activations (deleted, not only forgotten: whatever
    # else still names a buffer, the device lets go of it here)
    for n in model.cache_names:
        held = model.scope.get(n)
        model.scope.erase(n)
        held.delete()
    state_error = probe_errors(ctx, spec, tape, probes)
    del probes
    t_probe = time.perf_counter() - t_probe
    peak("state probe")
    t_check = time.perf_counter()
    gaps = _check_against_reference(ctx, model, tape, window)
    t_check = time.perf_counter() - t_check
    peak("reference")
    worst_gap = max(gaps) if gaps else float("inf")
    mean_gap = float(np.mean(gaps)) if gaps else float("inf")
    correct = bool(window and wrong_count == 0 and gaps
                   and worst_gap <= GAP_MAX_TOL
                   and mean_gap <= GAP_MEAN_TOL
                   and all(state_error[k] <= STATE_TOL[k]
                           for k in STATE_TOL)
                   and rounded <= BF16_SHARE_TOL
                   and not faults and misfits == 0
                   and disagree <= max(1, COMPLETION_MISMATCH_SHARE * done)
                   and compiles_in_window == 0 and new_misses == 0)
    model.executor.close()

    ctx.run.update(
        kind="serve", requests=window, all_requests=requests,
        t_ramp=t_ramp, first_calls=first_calls, engine_stats=stats,
        kv_reserved_bytes=reserved["kv"], state_reserved_bytes=reserved,
        kv_reserved_positions=spec.slots * spec.max_seq_len,
        slots=spec.slots)
    third = max(1, len(ttft) // 3)
    ctx.note(check=dict(reference_gap_max=worst_gap,
                        gap_max_tol=GAP_MAX_TOL,
                        reference_gap_mean=mean_gap,
                        gap_mean_tol=GAP_MEAN_TOL,
                        checked_tokens=len(gaps),
                        reference_seconds=t_check,
                        state_error=state_error, state_tol=STATE_TOL,
                        state_bf16_share=rounded,
                        bf16_share_tol=BF16_SHARE_TOL,
                        state_probe_seconds=t_probe,
                        not_the_reference_argmax=int(
                            np.count_nonzero(np.asarray(gaps) > 0)),
                        wrong_token_counts=wrong_count,
                        storage_faults=faults[:8],
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
             state_reserved_bytes=reserved, memory_peak_after=peaks,
             generator_lateness_ms=dict(
                 p50=percentile(lateness, 50) * 1e3,
                 max=max(lateness) * 1e3,
                 max_at_s=window[int(np.argmax(lateness))].due - t0)
             if lateness else None,
             queued_at_window_end=queued_at_end,
             ttft_ms=dict(p50=percentile(ttft, 50) * 1e3,
                          p95=percentile(ttft, 95) * 1e3,
                          by_third_p50=[
                              percentile(ttft[k:k + third], 50) * 1e3
                              for k in (0, third, 2 * third)
                              if ttft[k:k + third]]) if ttft else None,
             first_step_walls=[e - b for b, e in first_calls],
             engine=dict(steps=stats.get("steps"),
                         prefills=stats.get("prefills"),
                         shed=stats.get("shed")))
    return {
        "correct": correct, "attempted": len(window), "failed": failed,
        "end_to_end": {
            "serve_tokens_per_s": tokens / ctx.seconds,
            "tpot_ms_p95": percentile(tpot, 95) * 1e3 if tpot else 0.0},
        "program_temp_bytes": 0,
    }


def run_probes(ctx, model, spec) -> list:
    """The system's side of the state probe (the comment at PROBES):
    [(tokens [rows, n], {state name: what the probed slots hold of it,
    float32})], one entry a shape. What is kept is the probed slots'
    rows alone, so the caller can let go of the slots' arrays before
    the reference runs."""
    import jax.numpy as jnp
    rng = np.random.default_rng(ctx.seed)
    out = []
    for n_prefill, n_decode in (REHEARSAL_PROBES if ctx.rehearse
                                else PROBES):
        total = n_prefill + n_decode
        rows = min(PROBE_ROWS, spec.slots)
        tokens = rng.integers(1, spec.vocab_size, (rows, total))
        slots = np.sort(rng.permutation(spec.slots)[:rows])
        for row, slot in zip(tokens, slots):
            model.run_prefill(row[:n_prefill].tolist(), int(slot))
        for at in range(n_prefill, total):
            last, position, length = np.zeros((3, spec.slots), np.int64)
            last[slots], position[slots], length[slots] = \
                tokens[:, at], at, at + 1
            model.run_decode(last, position, spec.cache_buckets[0], length)
        held = {}
        for kind, names in model.state_kinds.items():
            for name in names:
                rows_of = jnp.asarray(model.scope.get(name))[
                    jnp.asarray(slots)]
                if kind == "kv":          # the positions written
                    rows_of = rows_of[:, :, :total]
                held[name] = rows_of.astype(jnp.float32)
        out.append((tokens, held))
    return out


def bf16_share(probes, names) -> float:
    """Of the non-zero values the probed slots hold in ``names``: the
    share that a bfloat16 holds whole (the low 16 bits of the float32
    are zero)."""
    import jax
    import jax.numpy as jnp
    whole = count = 0
    for _tokens, held in probes:
        for name in names:
            bits = jax.lax.bitcast_convert_type(held[name], jnp.uint32)
            live = (bits << 1) != 0                # neither 0.0 nor -0.0
            whole += int(jnp.sum(live & ((bits & 0xFFFF) == 0)))
            count += int(jnp.sum(live))
    return whole / count if count else 1.0


def probe_errors(ctx, spec, tape, probes) -> dict:
    """{kind: the largest ||system - reference|| / ||reference|| over
    probed rows and layers}: what ``run_probes`` kept against what the
    reference keeps of the same tokens, a layer at a time; and
    ``ssm_slow``: the same over the slow heads of every row and mamba
    layer of a shape together, the larger of the shapes' (inf where a
    run that is no rehearsal has no slow head to read)."""
    import jax.numpy as jnp
    states = resolve(ctx.config["reference"]["states"])
    arch = spec.arch["arch"]
    slow = {i: jnp.asarray(r <= SLOW_RATE) for i, r in
            resolve(ctx.config["reference"]["rates"])(tape, arch).items()}
    worst = {k: 0.0 for k in STATE_TOL}

    def note(kind, ours, theirs):
        axes = tuple(range(1, theirs.ndim))
        err = jnp.sqrt(jnp.sum((ours - theirs) ** 2, axes)
                       / jnp.sum(theirs ** 2, axes))
        worst[kind] = max(worst[kind], float(jnp.max(err)))

    for tokens, held in probes:
        rows = len(tokens)
        off = size = 0.0          # the slow heads' squared error and norm
        for i, kept in enumerate(states(tape, tokens, arch)):
            if arch["layer_types"][i] == "mamba":
                final, window = kept      # [rows,H,P,N], [rows,K-1,C]
                theirs = jnp.moveaxis(final, 3, 1)         # [rows,N,H,P]
                ours = held[f"ssm_state.l{i}"].reshape(theirs.shape)
                note("ssm", ours, theirs)
                note("conv", held[f"conv_state.l{i}"],
                     window.reshape(rows, -1))
                by_head = slow[i][None, None, :, None]
                off += float(jnp.sum(
                    jnp.where(by_head, ours - theirs, 0.0) ** 2))
                size += float(jnp.sum(
                    jnp.where(by_head, theirs, 0.0) ** 2))
            else:
                for which, theirs in zip("kv", kept):
                    note("kv", held[f"kv_cache.l{i}.{which}"], theirs)
        if size:
            worst["ssm_slow"] = max(worst["ssm_slow"],
                                    float(np.sqrt(off / size)))
        elif not ctx.rehearse:
            worst["ssm_slow"] = float("inf")
    return worst


def _check_against_reference(ctx, model, tape, window) -> list:
    """For CHECKED_REQUESTS completed requests, spread over the window
    (and so over the slots) from a seeded offset: how far below the
    reference's best logit each generated token sits, position by
    position — prompt, then decode through all three kinds of state.
    Requests are grouped by padded width (a power of two) and every
    batch goes through a layer before the next layer's weights are
    taken from the host."""
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
    batches, members = [], []
    for width, group in sorted(groups.items()):
        rows = max(1, REFERENCE_CHUNK_TOKENS // width)
        rows = min(rows, 1 << (len(group) - 1).bit_length())
        for i in range(0, len(group), rows):
            part = group[i:i + rows]
            tokens = np.ones((rows, width), np.int64)
            for row, (_r, full) in enumerate(part):
                tokens[row, :len(full)] = full
            batches.append(tokens)
            members.append(part)
    choice_gaps = resolve(ctx.config["reference"]["choice_gaps"])
    arch = model.spec.arch["arch"]
    out = []
    for part, gap in zip(members, choice_gaps(tape, batches, arch)):
        for row, (r, _full) in enumerate(part):
            p = len(r.prompt)
            # position p-1 predicts the first generated token, ...
            out.extend(gap[row, p - 1:p - 1 + r.answer_len].tolist())
    return out
