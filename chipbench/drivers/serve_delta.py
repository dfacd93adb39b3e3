"""Driver kind ``serve_delta``: a token server whose slots own a delta
rule's matrix state and three convolution windows a linear-attention
layer beside the full-attention layers' keys and values — the
``delta_hybrid`` family — under the same open loop as kinds ``serve``,
``serve_state`` and ``serve_experts`` (whose schedule, first-token
inference, waiters, percentile, grace, storage check, rounded-state
reading and reference grouping this file IMPORTS and does not copy; the
loop that offers the load is theirs line for line, because each is one
``run`` that cannot be called in part, and ``run_probes`` is
``serve_state``'s with this family's shapes, which that one reads from
its own module).

What differs from ``serve_state``:

* the family's spec: its architecture is the configuration's published
  keys, its storage table has a kind ``delta``;
* the state probe's shapes are this family's edges (one short of a
  prompt bucket; one past a chunk's edge, four chunks deep) and what it
  reads: ``kv``, ``conv`` (three windows a layer), ``delta`` and
  ``delta_slow`` — the matrix state of the heads whose alpha is nearest
  1 after 600 decode steps;
* the heap is frozen before the engine starts, as ``serve_experts``
  freezes it;
* ``--set control=<name>`` runs one of ``CONTROLS``, a deliberate fault
  each of which must read ``correct: false``; ``--set skip_checks=true``
  a sweep's run without probe and reference. The driver's check passes
  neither.
"""
from __future__ import annotations

import contextlib
import gc
import threading
import time

import numpy as np

from .. import weights
from . import resolve, sizes
from ..traffic import serve as traffic
from .serve import (COMPLETION_MISMATCH_SHARE, COMPLETION_SLACK_S, DECODE,
                    _await, match_first_tokens, percentile)
from .serve_experts import _Swapped
from .serve_state import (BF16_SHARE_TOL, GRACE_S, PROBE_ROWS,
                          RESEED_ARRAYS, _check_against_reference,
                          bf16_share, storage_faults)

# (tokens prefilled, tokens decoded): 127 of a bucket of 128 (one pad
# row, the last chunk one short), then 28 steps; 257 — four chunks and
# one row past the edge, in the 1024 bucket with its flash kernel — then
# 600 steps, which is where a slow head's state shows what its storage
# dropped (serve_state.py's comment at PROBES)
PROBES = ((127, 28), (257, 600))
REHEARSAL_PROBES = ((7, 3), (9, 20))
# a head is slow where softplus(dt_bias) exp(A_log), what it forgets of
# its state a step when its projection adds nothing, is at most this
SLOW_RATE = 0.01
# The limits, each between what the change read over its seeds and what
# the controls read (my chip runs, PR 58: PERF.md section 6 has every
# reading). Of the window's checked requests, every generated token's
# reference logit against the reference's best (logits spread 0.27: a
# final norm's unit rows through Xavier columns): the WORST gap — the
# change 0.0079-0.0116 over 9 runs; the controls 0.30-1.75 — and the
# MEAN gap — the change 5.3e-5 to 7.3e-5 (2.4 tokens in 100 are not the
# reference's own, at near-ties); pad rows advancing the state 1.0e-3,
# the window shifted 2.2e-3, the rest 0.018-0.82. Of the probe rows, by
# kind, the largest ||system - reference|| / ||reference|| over rows
# and layers: ``kv`` 0.0124-0.0125 (the window shifted 0.106, beta
# unscaled 0.18, the read before the decay 0.27), ``conv`` 0.0105-0.0136
# (0.17 and 0.33: a shifted window itself is gone from the slot three
# steps after its prefill, and what the probe reads 28 and 600 steps
# later is the stream), ``delta`` 0.026-0.039 (0.29, 0.65, 0.82) —
# bfloat16 activations, 16 layers deep, through a rule that writes a
# DIFFERENCE (v - r) — and ``delta_slow``, the same over the slow heads
# of a shape together: 0.0169-0.0185 (0.55 and 0.56). Pad rows
# advancing the state are seen by the gaps alone (the probe's 1 and 767
# pad rows are forgotten 28 and 600 steps later: 0.053), keys and
# queries not normalised by the gaps (a state that overflows is no
# number: inf here), and a state rounded where it is stored by NOTHING
# but the share of the probed matrix state's non-zero values that carry
# nothing below bfloat16's 8 bits (serve_state.py BF16_SHARE_TOL: the
# change 2.7e-5 to 3.0e-5, the rounded state 1.0; its errors read what
# the change's read — a delta rule corrects what it reads, rounding
# included — 0.0345 and 0.0169).
GAP_MAX_TOL = 0.06
GAP_MEAN_TOL = 2.5e-4
STATE_TOL = {"kv": 0.035, "conv": 0.04, "delta": 0.12, "delta_slow": 0.05}


# -- the controls -----------------------------------------------------------

class _Attrs(_Swapped):
    """An op's context with some attrs replaced."""

    def __init__(self, ctx, attrs):
        super().__init__(ctx)
        self._attrs = attrs

    def attr(self, name, default=None):
        return self._attrs.get(name, self._ctx.attr(name, default))


def _rounded(x):
    """x at bfloat16's 8 bits of mantissa, in its own dtype. Not a cast
    there and back: the TPU compiler drops such a pair (it may keep
    excess precision) and the state would stay what it was."""
    import jax
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _read_before_decay(ctx, _rule):
    """gated_delta_state_update with r = S^T k taken from the state
    BEFORE it is decayed (a state-space scan's order)."""
    import jax.numpy as jnp
    from paddle_tpu.ops import delta_ops
    state, x = ctx.input("State"), ctx.input("V")
    slots = x.shape[0]
    q, k, v, g, beta = delta_ops.step_inputs(ctx, (slots,))
    held = state.astype(jnp.float32).reshape(slots, state.shape[1],
                                             q.shape[1], -1)
    r = jnp.einsum("skhv,shk->shv", held, k,
                   precision=delta_ops.HIGHEST)
    d = beta[..., None] * (v - r)
    new = held * jnp.exp(g)[:, None, :, None] + jnp.einsum(
        "shk,shv->skhv", k, d, precision=delta_ops.HIGHEST)
    o = jnp.einsum("skhv,shk->shv", new, q, precision=delta_ops.HIGHEST)
    ctx.set_output("Out", o.reshape(x.shape).astype(x.dtype))
    ctx.set_output("StateOut", new.reshape(state.shape).astype(state.dtype))


def _faulty_rules(control):
    """{op type: rule(ctx, sound rule)} of a control."""
    import jax.numpy as jnp

    def state_rounded(slot):
        return lambda ctx, rule: rule(_Swapped(ctx,
                                               outputs={slot: _rounded}))

    def beta_unscaled(ctx, rule):
        return rule(_Attrs(ctx, {"beta_scale": 1.0}))

    def pad_rows(ctx, rule):
        rows = ctx.input("V").shape[1]
        return rule(_Swapped(ctx, {"Length": lambda n: jnp.full_like(
            n, rows)}))

    def window_shifted(ctx, rule):
        return rule(_Swapped(ctx, {"Length": lambda n: jnp.maximum(
            n - 1, 0)}))

    return {
        "state_bfloat16": {
            "gated_delta_prefill": state_rounded("State"),
            "gated_delta_state_update": state_rounded("StateOut")},
        "beta_unscaled": {"gated_delta_prefill": beta_unscaled,
                          "gated_delta_state_update": beta_unscaled},
        "read_before_decay": {
            "gated_delta_state_update": _read_before_decay},
        "pad_rows_advance": {"gated_delta_prefill": pad_rows},
        "window_shifted": {"causal_conv1d": window_shifted},
        "qk_not_normalised": {},         # a function of the op module
    }[control]


CONTROLS = ("state_bfloat16", "beta_unscaled", "read_before_decay",
            "pad_rows_advance", "window_shifted", "qk_not_normalised")


@contextlib.contextmanager
def faulty(control):
    """The op rules of a control in place of the program's, for as long
    as the programs of a run are traced; nothing where ``control`` is
    None."""
    from paddle_tpu.core.registry import OpRegistry
    from paddle_tpu.ops import delta_ops
    sound = {}
    unit_rows = delta_ops.unit_rows
    if control is not None:
        for op_type, rule in _faulty_rules(control).items():
            opdef = OpRegistry.get(op_type)
            sound[op_type] = opdef.compute
            opdef.compute = (lambda ctx, rule=rule, was=opdef.compute:
                             rule(ctx, was))
    if control == "qk_not_normalised":
        import jax.numpy as jnp
        delta_ops.unit_rows = lambda x, heads, eps, scale=1.0: \
            x.astype(jnp.float32).reshape(x.shape[:-1] + (heads, -1)) \
            * scale
    try:
        yield
    finally:
        delta_ops.unit_rows = unit_rows
        for op_type, rule in sound.items():
            OpRegistry.get(op_type).compute = rule


# -- the spec -----------------------------------------------------------------

def build_spec(config: dict, spec_args: dict, slots: int, rehearse: bool):
    """The GenerationSpec of a configuration file whose top level holds
    the published keys (a rehearsal's toy sizes over them)."""
    from paddle_tpu.models.delta_hybrid import ARCH_KEYS
    from paddle_tpu.serving.generation import GenerationSpec
    arch = {k: config[k] for k in ARCH_KEYS}
    if rehearse:
        arch.update(config["rehearse"]["arch"])
    family = dict(arch=arch, dtypes=dict(config["storage_dtypes"]),
                  embedding_std=config["assumed_values"]["embedding_std"])
    return GenerationSpec(**dict(spec_args, slots=slots,
                                 family=config["builder"]["family"],
                                 arch=family))


# -- the probe ----------------------------------------------------------------

def run_probes(ctx, model, spec) -> list:
    """The system's side of the state probe: [(tokens [rows, n], {state
    name: what the probed slots hold of it, float32})], one entry a
    shape of PROBES — a prefill of the first tokens into slots of their
    own, then the rest one decode step a token. What is kept is the
    probed slots' rows alone."""
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


def probe_errors(ctx, spec, tape, probes) -> dict:
    """{kind: the largest ||system - reference|| / ||reference|| over
    probed rows and layers}: what ``run_probes`` kept against what the
    reference keeps of the same tokens, a layer at a time; and
    ``delta_slow``: the same over the slow heads of every row and linear
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
        # a state that is no number is as far off as a state can be
        worst[kind] = max(worst[kind], float(jnp.max(
            jnp.where(jnp.isnan(err), jnp.inf, err))))

    for tokens, held in probes:
        rows = len(tokens)
        off = size = 0.0          # the slow heads' squared error and norm
        for i, kept in enumerate(states(tape, tokens, arch)):
            if arch["layer_types"][i] == "linear_attention":
                final, windows = kept     # [rows,H,K,V], {w: [rows,3,C]}
                theirs = jnp.moveaxis(final, 1, 2)         # [rows,K,H,V]
                ours = held[f"delta_state.l{i}"].reshape(theirs.shape)
                note("delta", ours, theirs)
                for which, window in windows.items():
                    note("conv", held[f"conv_state.l{i}.{which}"],
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
            slow_error = float(np.sqrt(off / size))
            worst["delta_slow"] = max(
                worst["delta_slow"],
                float("inf") if np.isnan(slow_error) else slow_error)
        elif not ctx.rehearse:
            worst["delta_slow"] = float("inf")
    return worst


# -- a run --------------------------------------------------------------------

def run(ctx) -> dict:
    import paddle_tpu as pt

    spec_args, tr = sizes(ctx.config, ctx.workload, ctx.rehearse)
    control = ctx.workload.get("control")
    if control is not None and control not in CONTROLS:
        raise ValueError(f"control {control!r}: one of {CONTROLS}")
    pt.reset_default_programs()
    pt.reset_global_scope()
    pt.amp.enable(False)
    spec = build_spec(ctx.config, spec_args, int(tr["slots"]),
                      ctx.rehearse)
    if tr["prompt_len"]["max"] + tr["answer_len"]["max"] > spec.max_seq_len:
        raise ValueError("the longest prompt and answer do not fit "
                         "max_seq_len: such a request retires early")
    with faulty(control):
        return _run(ctx, spec, tr, control)


def _run(ctx, spec, tr, control) -> dict:
    from paddle_tpu.serving.generation import (GenerationConfig,
                                               GenerationModel)
    peaks = []                    # (after what, the allocator's peak)

    def peak(what):
        st = ctx.devices[0].memory_stats() or {}
        peaks.append((what, int(st.get("peak_bytes_in_use") or 0)))

    model = GenerationModel.build(spec)
    peak("startup")
    lm = model.programs["prefill"][spec.prompt_buckets[0]]
    every = lm.main.all_parameters()
    params = [p.name for p in every]
    # a few arrays a call: one call over all 4.1 G weights holds their
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
    # what set-up built stays out of the window's garbage collections
    # (serve_experts.py; ROADMAP A1(c))
    gc.collect()
    gc.freeze()
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
        # before the trace is stopped: that takes tens of seconds, in
        # which the engine goes on stepping
        at_close = engine.stats()
        ctx.close_window(t1)
        for th in waiters:
            th.join()
    finally:
        engine.stop(drain=False, timeout=60)
        gc.unfreeze()
    peak("window")
    stats = engine.stats()
    spans = list(ctx.spans.spans)
    misfits = match_first_tokens(requests, spans)
    steps = [s.dur for s in ctx.spans.named(DECODE, t0, t1)]
    step_s = float(np.median(steps)) if steps else 0.0

    window = [r for r in requests if r.in_window]
    ttft, tpot, tokens, failed = [], [], 0, 0
    wrong_count = disagree = repeats = 0
    slowest = []      # (ms a token, prompt, answer, first token at) a reply
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
        out = r.result.tokens
        tokens += len(out)
        repeats += sum(a == b for a, b in zip(out, out[1:]))
        ttft.append(r.first_token - r.due)
        tpot.append((r.completed - r.first_token) / (r.answer_len - 1))
        slowest.append((tpot[-1] * 1e3, len(r.prompt), r.answer_len,
                        r.first_token - t0))
    lateness = [r.submitted - r.due for r in window]
    done = len(window) - failed

    compiles_in_window = ctx.spans.compile_count(t0, t1)
    new_misses = model.executor.cache_stats["misses"] - misses0
    faults = storage_faults(model, ctx.config["storage_dtypes"])
    reserved = model.state_bytes()
    t_probe = time.perf_counter()
    # a sweep of the rate reads the queue and the tails alone
    sweep = bool(ctx.workload.get("skip_checks"))
    probes = [] if sweep else run_probes(ctx, model, spec)
    rounded = bf16_share(probes, model.state_kinds["delta"])
    tape = [] if sweep else [np.asarray(model.scope.get(n))
                             for n in params]
    # the model is done with: its 14 GB make room for the reference's
    # float32 layers and activations (deleted, not only forgotten)
    for n in list(model.cache_names) + params:
        held = model.scope.get(n)
        model.scope.erase(n)
        held.delete()
    state_error = dict.fromkeys(STATE_TOL, 0.0) if sweep else \
        probe_errors(ctx, spec, tape, probes)
    del probes
    t_probe = time.perf_counter() - t_probe
    peak("state probe")
    t_check = time.perf_counter()
    gaps = [] if sweep else _check_against_reference(ctx, model, tape,
                                                     window)
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
        slots=spec.slots, cache_buckets=list(spec.cache_buckets))
    third = max(1, len(ttft) // 3)
    ctx.note(check=dict(control=control,
                        reference_gap_max=worst_gap,
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
                        repeats_of_the_last_token=repeats,
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
             # the tail that sets tpot_ms_p95: a short reply that
             # shared its life with a long prompt's prefill
             slowest_replies=[[round(x, 3) for x in row] for row in
                              sorted(slowest, reverse=True)[:8]],
             queued_at_window_end=at_close["queued"],
             active_at_window_end=at_close["active"],
             gc_ms_in_window=[round(g.dur * 1e3, 1) for g in
                              ctx.spans.named("runtime::gc", t0, t1)],
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
