"""Driver kind ``serve_experts``: a token server whose layers route every
token to one of several experts and whose slots own a compressed KV
cache beside convolution windows — the ``cca_moe`` family — under the
same open loop as kinds ``serve`` and ``serve_state`` (whose schedule,
first-token inference, waiters, percentile, grace, state probe driver
and reference grouping this file IMPORTS and does not copy; the loop
that offers the load is theirs line for line, because each is one
``run`` that cannot be called in part).

What differs from ``serve_state``:

* the family's spec and storage table: the router's arrays are float32
  beside the norm scales, every other matrix at the weights' width;
* a top-1 pick at a near-tie is decided by bfloat16 rounding and either
  expert is correct, so the reference also gives every position's
  smallest routing MARGIN ``p_1 - p_2`` over the layers, and the logit
  gaps are judged on the tokens whose margin exceeds ``MARGIN_MIN``; the
  share of tokens set aside is printed and limited;
* the probe rows' cache and windows are set against the reference's by
  kind, and the probe rows' PICKS, layer by layer, must be the
  reference's wherever the margin exceeds the threshold (the programs
  report their picks with their tokens: models/cca_moe.py);
* the experts' stored values are read for how many bits they carry;
* the window's expert counters (rows sent, experts read) are kept for
  the per-layer readers;
* ``--set control=<name>`` runs one of ``CONTROLS``, a deliberate fault
  each of which must read ``correct: false``; the driver's check never
  passes it.
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
from .serve_state import (GRACE_S, RESEED_ARRAYS, _check_against_reference,
                          run_probes)

# A position whose smallest routing margin over the layers is at most
# this is set aside: the pick there hangs on rounding. Of the probe's
# (layer, position) pairs 2.4 % lie at or under it, so of the tokens —
# twenty layers each — 39-42 %; above it 0.26 % of the system's picks
# are still not the reference's own (bfloat16 activations move a
# router's probabilities by up to 0.012: ``deficit``).
MARGIN_MIN = 0.002
# Limits, each between what the change read over its seeds and what the
# controls read (my chip runs, PR 53: PERF.md section 6 has every
# reading). Of the window's checked requests, judged on the tokens above
# the margin under the reference's OWN routing — so a pick that rounding
# flipped still moves every later logit, and these two are coarse: the
# WORST reference logit gap (the change 0.29-1.11 over 21 runs; five
# controls 2.9-6.9, but 1.5 with the carried term dropped, 0.93 with
# float8 experts and 0.68 with the window one row early, which other
# limits refuse) and the MEAN gap (the change 0.0016-0.0026; float8
# experts 0.020, pad rows in the windows 0.022, the rest 0.057-2.1,
# the early window 0.0025); the share of checked tokens set aside
# (0.375-0.42; it guards the margin itself). Of
# the probe rows, under the SYSTEM's picks: by kind the largest relative
# error ||system - reference|| / ||reference|| of their state — ``kv`` a
# POSITION at a time (one wrong row of a cache does not hide among a
# thousand right ones), ``conv`` a window at a time — the change
# 0.017-0.018 and 0.015-0.018, the controls' lowest 0.14 (float8), 0.27, 0.48 and
# 0.13, 0.16, 0.21; the share of picks that are not the reference's own
# where its margin exceeds the threshold (0.0024-0.0029; 0.048 float8,
# 0.063 rotation, 0.44 and 0.99 the two routing controls) and the most a
# pick's probability lies under the reference's best (0.007-0.016;
# 0.083 float8, 0.10 the early window, 0.20-0.90 the rest); and the
# share of the experts' stored non-zero values whose low four bits of
# mantissa are zero (0.061, a sixteenth of a bfloat16 draw's; 1.0 of a
# float8's: the one reading that tells float8 experts by itself).
GAP_MAX_TOL = 2.0
GAP_MEAN_TOL = 0.008
SET_ASIDE_MAX = 0.6
STATE_TOL = {"kv": 0.08, "conv": 0.08}
PICK_MISMATCH_MAX = 0.01
PICK_DEFICIT_MAX = 0.04
LOW_BITS_SHARE_MAX = 0.2


# -- the controls -----------------------------------------------------------

class _Swapped:
    """An op's context with some inputs and outputs passed through a
    function first."""

    def __init__(self, ctx, inputs=None, outputs=None):
        self._ctx, self._in, self._out = ctx, inputs or {}, outputs or {}

    def __getattr__(self, name):
        return getattr(self._ctx, name)

    def input(self, slot):
        value = self._ctx.input(slot)
        return self._in[slot](value) if slot in self._in else value

    def set_output(self, slot, value, index=0):
        if slot in self._out:
            value = self._out[slot](value)
        self._ctx.set_output(slot, value, index)


def _faulty_rules(control, arch):
    """{op type: rule(ctx, sound rule)} of a control."""
    import jax.numpy as jnp
    shifted = arch["num_key_value_heads"] // 2 * arch["head_dim"]

    def before_last(length):                 # the window one row early
        return jnp.maximum(length - 1, 0)

    def window_shifted(ctx, rule):
        if ctx.input("X").shape[-1] == shifted:
            return rule(ctx)                 # the value's shift: sound
        return rule(_Swapped(ctx, {"Length": before_last}))

    def shift_dropped(ctx, rule):
        if ctx.input("X").shape[-1] != shifted:
            return rule(ctx)
        x = ctx.input("X")
        return rule(_Swapped(ctx, outputs={"Out": lambda _o: x}))

    def rotation_at_zero(ctx, rule):
        if ctx.input("X").ndim != 3:         # a prompt's rows: sound
            return rule(ctx)
        return rule(_Swapped(ctx, {"Positions": jnp.zeros_like}))

    def picks_moved(ctx, rule):
        experts = ctx.input("W3").shape[-1]
        return rule(_Swapped(ctx, outputs={"TopIdx": lambda i: jnp.where(
            i >= 0, (i + 1) % experts, i)}))

    def carried_dropped(ctx, rule):
        return rule(_Swapped(ctx, {"RPrev": lambda _r: None}))

    def pad_rows(ctx, rule):
        rows = ctx.input("X").shape[1]
        return rule(_Swapped(ctx, {"Length": lambda n: jnp.full_like(
            n, rows)}))

    return {
        "window_shifted": {"causal_conv1d": window_shifted},
        "value_shift_dropped": {"causal_conv1d": shift_dropped,
                                "conv_state_update": shift_dropped},
        "rotation_at_zero": {"rotary_embedding": rotation_at_zero},
        "picks_moved": {"mlp_router": picks_moved},
        "carried_term_dropped": {"mlp_router": carried_dropped},
        "pad_rows_in_windows": {"causal_conv1d": pad_rows,
                                "grouped_causal_conv1d": pad_rows},
        "float8_experts": {},                # the weights, not a rule
    }[control]


CONTROLS = ("float8_experts", "window_shifted", "value_shift_dropped",
            "rotation_at_zero", "picks_moved", "carried_term_dropped",
            "pad_rows_in_windows")


@contextlib.contextmanager
def faulty(control, arch):
    """The op rules of a control in place of the program's, for as long
    as the programs of a run are traced; nothing where ``control`` is
    None."""
    from paddle_tpu.core.registry import OpRegistry
    sound = {}
    if control is not None:
        for op_type, rule in _faulty_rules(control, arch).items():
            opdef = OpRegistry.get(op_type)
            sound[op_type] = opdef.compute
            opdef.compute = (lambda ctx, rule=rule, was=opdef.compute:
                             rule(ctx, was))
    try:
        yield
    finally:
        for op_type, rule in sound.items():
            OpRegistry.get(op_type).compute = rule


def round_experts_to_float8(model):
    """Control ``float8_experts``: every expert matrix through
    float8_e4m3fn and back into its bfloat16 array, 3 bits of mantissa
    where the configuration states 7."""
    import jax.numpy as jnp
    for name in _expert_arrays(model):
        w = model.scope.get(name)
        model.scope.set(name, w.astype(jnp.float8_e4m3fn).astype(w.dtype))


# -- the spec, the storage ---------------------------------------------------

def build_spec(config: dict, spec_args: dict, slots: int, rehearse: bool):
    """The GenerationSpec of a configuration file whose top level holds
    the published keys (a rehearsal's toy sizes over them)."""
    from paddle_tpu.models.cca_moe import ARCH_KEYS
    from paddle_tpu.serving.generation import GenerationSpec
    arch = {k: config[k] for k in ARCH_KEYS}
    std = config["assumed_values"]["embedding_std"]
    if rehearse:
        arch.update(config["rehearse"]["arch"])
        std = config["rehearse"]["embedding_std"]
    family = dict(arch=arch, dtypes=dict(config["storage_dtypes"]),
                  embedding_std=std)
    return GenerationSpec(**dict(spec_args, slots=slots,
                                 family=config["builder"]["family"],
                                 arch=family))


def _parameters(model):
    lm = model.programs["prefill"][model.spec.prompt_buckets[0]]
    return lm.main.all_parameters()


def _expert_arrays(model) -> list:
    return [p.name for p in _parameters(model)
            if p.name.startswith("moe_experts")]


def storage_faults(model, table: dict) -> list:
    """Every stored array whose dtype is not what the configuration's
    table gives its kind: per-slot state by kind; the router's arrays
    ``scales``; of the other parameters the 2-D ones ``weights`` and the
    1-D ones ``weights`` (a convolution's bias) or ``scales`` (norm
    scales, tau)."""
    faults = []
    for kind, names in model.state_kinds.items():
        for n in names:
            have = str(model.scope.get(n).dtype)
            if have != table[kind]:
                faults.append((n, have, table[kind]))
    for p in _parameters(model):
        have = str(model.scope.get(p.name).dtype)
        if p.name.startswith("router"):
            want = (table["scales"],)
        elif len(p.shape) == 2:
            want = (table["weights"],)
        else:
            want = (table["weights"], table["scales"])
        if have not in want:
            faults.append((p.name, have, " or ".join(want)))
    return faults


def low_bits_share(model) -> float:
    """Of the non-zero values the first and the last layer's experts
    store: the share whose low four bits of mantissa are zero."""
    import jax
    import jax.numpy as jnp
    names = _expert_arrays(model)
    zero = count = 0
    for name in names[:3] + names[-3:]:
        bits = jax.lax.bitcast_convert_type(
            model.scope.get(name).astype(jnp.bfloat16), jnp.uint16)
        live = (bits << 1) != 0
        zero += int(jnp.sum(live & ((bits & 0xF) == 0)))
        count += int(jnp.sum(live))
    return zero / count if count else 1.0


# -- the probe ----------------------------------------------------------------

class _Watched:
    """The model as serve_state.run_probes drives it, keeping what each
    run reported of its picks: (slot, [layers, tokens]) a prefill,
    [layers, slots] a decode step."""

    def __init__(self, model):
        self._model, self.prefills, self.steps = model, [], []

    def __getattr__(self, name):
        return getattr(self._model, name)

    def run_prefill(self, prompt, slot):
        out = self._model.run_prefill(prompt, slot)
        self.prefills.append(
            (slot, self._model.last_observed["picks"][:, :len(prompt)]))
        return out

    def run_decode(self, *args):
        out = self._model.run_decode(*args)
        self.steps.append(self._model.last_observed["picks"])
        return out


def probe(ctx, model, spec) -> list:
    """[(tokens [rows, n], {state name: the probed slots' rows}, picks
    [layers, rows, n])], one entry a shape of serve_state.PROBES."""
    watched = _Watched(model)
    out = []
    for tokens, held in run_probes(ctx, watched, spec):
        rows = len(tokens)
        first, watched.prefills = watched.prefills[:rows], \
            watched.prefills[rows:]
        slots = [slot for slot, _ in first]
        n_decode = tokens.shape[1] - first[0][1].shape[1]
        steps, watched.steps = watched.steps[:n_decode], \
            watched.steps[n_decode:]
        picks = np.concatenate(
            [np.stack([p for _, p in first], axis=1)]
            + [s[:, slots][:, :, None] for s in steps], axis=2)
        out.append((tokens, held, picks))
    return out


def probe_errors(ctx, spec, tape, probes) -> dict:
    """What the probed slots hold against what the reference keeps of
    the same tokens UNDER THE SAME PICKS (the reference's routing is
    forced to the system's, so that a pick at a near-tie, which
    rounding decides, does not count against every key after it):
    {"kv", "conv": the largest relative error, a position (a window) at
    a time; "picks": the share of picks that are not the reference's
    own where its margin exceeds MARGIN_MIN; "deficit": the most any
    pick's probability lies under the reference's best; "near_ties":
    the share of the probe's picks at or under the margin}."""
    import jax.numpy as jnp
    states = resolve(ctx.config["reference"]["states"])
    arch = spec.arch["arch"]
    worst = {"kv": 0.0, "conv": 0.0, "deficit": 0.0}
    wrong = judged = total = 0

    def note(kind, ours, theirs, axes):
        err = jnp.sqrt(jnp.sum((ours - theirs) ** 2, axes)
                       / jnp.sum(theirs ** 2, axes))
        worst[kind] = max(worst[kind], float(jnp.max(err)))

    for tokens, held, picks in probes:
        for i, kept in enumerate(states(tape, tokens, arch, picks)):
            for which in "kv":
                note("kv", held[f"kv_cache.l{i}.{which}"], kept[which],
                     (1, 3))                     # [rows, c, S, d_h]
            for window, theirs in (("z", "z"), ("a", "a"), ("v", "v2")):
                note("conv", held[f"conv_state.l{i}.{window}"],
                     kept[theirs], (1,))
            clear = kept["margin"] > MARGIN_MIN
            worst["deficit"] = max(worst["deficit"],
                                   float(kept["deficit"].max()))
            # forced, the reference hands the picks back: its own
            # choice is the pick without a deficit
            wrong += int(np.sum(clear & (kept["deficit"] > 0)))
            judged += int(np.sum(clear))
            total += clear.size
    return dict(worst, picks=wrong / judged if judged else 1.0,
                near_ties=1.0 - judged / total if total else 1.0)


# -- a run --------------------------------------------------------------------

def _expert_counts(stats) -> dict:
    return dict(rows=sum(stats.get("expert_rows_by_expert", {}).values()),
                experts_read=stats.get("experts_read", 0),
                steps=stats["steps"])


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
    with faulty(control, spec.arch["arch"]):
        return _run(ctx, spec, tr, control)


def _run(ctx, spec, tr, control) -> dict:
    from paddle_tpu.serving.generation import (GenerationConfig,
                                               GenerationModel)
    arch = spec.arch["arch"]
    peaks = []                    # (after what, the allocator's peak)

    def peak(what):
        st = ctx.devices[0].memory_stats() or {}
        peaks.append((what, int(st.get("peak_bytes_in_use") or 0)))

    model = GenerationModel.build(spec)
    peak("startup")
    every = _parameters(model)
    params = [p.name for p in every]
    # a few arrays a call: one call over all the weights holds their
    # random bits (4 bytes a weight) beside the slots' state
    for i in range(0, len(every), RESEED_ARRAYS):
        weights.reseed(model.scope, every[i:i + RESEED_ARRAYS],
                       ctx.seed + i)
    tape = None
    if control == "float8_experts":
        # the reference reads what the configuration states
        tape = [np.asarray(model.scope.get(n)) for n in params]
        round_experts_to_float8(model)
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
    # what set-up built — five programs of 20 layers, their jaxprs and
    # executables: millions of objects — stays out of the window's
    # garbage collections, each of which walked it for 0.24-0.33 s with
    # every request in flight waiting (ROADMAP A1(c))
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
    counts_open = None
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
                counts_open = _expert_counts(engine.stats())
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
            counts_open = _expert_counts(engine.stats())
            ctx.open_window(at=t0)
        wait = t1 - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        # before the trace is stopped: that takes tens of seconds, in
        # which the engine goes on stepping
        at_close = engine.stats()
        ctx.close_window(t1)
        queued_at_end = at_close["queued"]
        for th in waiters:
            th.join()
    finally:
        engine.stop(drain=False, timeout=60)
        gc.unfreeze()
    peak("window")
    counts_close = _expert_counts(at_close)
    in_window = {k: counts_close[k] - counts_open[k] for k in counts_close}
    stats = engine.stats()
    spans = list(ctx.spans.spans)
    misfits = match_first_tokens(requests, spans)
    steps = [s.dur for s in ctx.spans.named(DECODE, t0, t1)]
    step_s = float(np.median(steps)) if steps else 0.0

    window = [r for r in requests if r.in_window]
    ttft, tpot, tokens, failed = [], [], 0, 0
    wrong_count = disagree = repeats = 0
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
        out = r.result.tokens
        repeats += sum(a == b for a, b in zip(out, out[1:]))
        ttft.append(r.first_token - r.due)
        tpot.append((r.completed - r.first_token) / (r.answer_len - 1))
    lateness = [r.submitted - r.due for r in window]
    done = len(window) - failed

    compiles_in_window = ctx.spans.compile_count(t0, t1)
    new_misses = model.executor.cache_stats["misses"] - misses0
    faults = storage_faults(model, ctx.config["storage_dtypes"])
    low_bits = low_bits_share(model)
    reserved = model.state_bytes()
    t_probe = time.perf_counter()
    # a sweep of the rate reads the queue and the tails alone
    sweep = bool(ctx.workload.get("skip_checks"))
    probes = [] if sweep else probe(ctx, model, spec)
    if tape is None:
        tape = [np.asarray(model.scope.get(n)) for n in params]
    # the model is done with: its 13 GB make room for the reference's
    # float32 layers and activations (deleted, not only forgotten)
    for n in list(model.cache_names) + params:
        held = model.scope.get(n)
        model.scope.erase(n)
        held.delete()
    state_error = probe_errors(ctx, spec, tape, probes)
    del probes
    t_probe = time.perf_counter() - t_probe
    peak("state probe")
    t_check = time.perf_counter()
    pairs = np.asarray([] if sweep else _check_against_reference(
        ctx, model, tape, window), np.float64).reshape(-1, 2)
    t_check = time.perf_counter() - t_check
    peak("reference")
    gaps = pairs[pairs[:, 1] > MARGIN_MIN, 0]
    set_aside = 1.0 - len(gaps) / len(pairs) if len(pairs) else 1.0
    worst_gap = float(gaps.max()) if len(gaps) else float("inf")
    mean_gap = float(gaps.mean()) if len(gaps) else float("inf")
    correct = bool(window and wrong_count == 0 and len(gaps)
                   and worst_gap <= GAP_MAX_TOL
                   and mean_gap <= GAP_MEAN_TOL
                   and set_aside <= SET_ASIDE_MAX
                   and all(state_error[k] <= STATE_TOL[k]
                           for k in STATE_TOL)
                   and state_error["picks"] <= PICK_MISMATCH_MAX
                   and state_error["deficit"] <= PICK_DEFICIT_MAX
                   and low_bits <= LOW_BITS_SHARE_MAX
                   and not faults and misfits == 0
                   and disagree <= max(1, COMPLETION_MISMATCH_SHARE * done)
                   and compiles_in_window == 0 and new_misses == 0)
    model.executor.close()

    ctx.run.update(
        kind="serve", requests=window, all_requests=requests,
        t_ramp=t_ramp, first_calls=first_calls, engine_stats=stats,
        kv_reserved_bytes=reserved["kv"], state_reserved_bytes=reserved,
        kv_reserved_positions=spec.slots * spec.max_seq_len,
        slots=spec.slots, experts_in_window=in_window,
        expert_layers=len(arch["layer_types"]),
        cache_buckets=list(spec.cache_buckets))
    third = max(1, len(ttft) // 3)
    ctx.note(check=dict(control=control,
                        reference_gap_max=worst_gap,
                        reference_gap_mean=mean_gap,
                        gap_max_tol=GAP_MAX_TOL, gap_mean_tol=GAP_MEAN_TOL,
                        checked_tokens=len(pairs), judged_tokens=len(gaps),
                        set_aside_share=set_aside,
                        set_aside_max=SET_ASIDE_MAX,
                        margin_min=MARGIN_MIN,
                        reference_seconds=t_check,
                        state_error=state_error, state_tol=STATE_TOL,
                        pick_mismatch_max=PICK_MISMATCH_MAX,
                        pick_deficit_max=PICK_DEFICIT_MAX,
                        experts_low_bits_share=low_bits,
                        low_bits_share_max=LOW_BITS_SHARE_MAX,
                        state_probe_seconds=t_probe,
                        not_the_reference_argmax=int(
                            np.count_nonzero(gaps > 0)),
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
             experts=dict(in_window=in_window,
                          rows_by_expert=stats.get(
                              "expert_rows_by_expert")),
             state_reserved_bytes=reserved, memory_peak_after=peaks,
             generator_lateness_ms=dict(
                 p50=percentile(lateness, 50) * 1e3,
                 max=max(lateness) * 1e3,
                 max_at_s=window[int(np.argmax(lateness))].due - t0)
             if lateness else None,
             queued_at_window_end=queued_at_end,
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
