"""Control-flow ops: sub-blocks lowered to lax.scan / while_loop / cond.

Reference parity: paddle/fluid/operators/{while_op.cc:35, recurrent_op.cc:222,
conditional_block_op.cc, tensor_array_read_write_op.cc}. The reference runs
sub-blocks with nested Executors and per-step scopes; here a sub-block is
traced into the parent's XLA computation as a structured-control-flow region,
so the whole loop compiles to one fused TPU program (grad flows through via
jax.vjp of the scan/while, replacing the reference's WhileGrad/RecurrentGrad
step-scope machinery).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from functools import partial

from ..core.registry import OpRegistry, register_op

register_op_CF = partial(register_op, ragged_aware=True)


def _trace_sub(ctx, block_idx, env):
    from ..core.executor import trace_block
    prog = ctx.extra["program"]
    return trace_block(prog.blocks[block_idx], env, ctx.extra)


def nested_dynamic_wids(program, blk_idx):
    """while_ids of every unbounded (dynamic_bound) While nested
    anywhere under block `blk_idx`, in deterministic program order.
    Static program structure — safe to bake into carry shapes."""
    out = []

    def visit(bi):
        for op in program.blocks[bi].ops:
            if op.type == "while" and op.attrs.get("dynamic_bound") and \
                    int(op.attrs.get("max_steps", 0) or 0) <= 0:
                out.append(op.attrs.get("while_id"))
            for attr in ("sub_block_idx", "true_block_idx",
                         "false_block_idx"):
                idx = op.attrs.get(attr)
                if isinstance(idx, int):
                    visit(idx)

    visit(blk_idx)
    return out


def union_nested_wids(program, blk_idxs):
    """Deduped union of nested_dynamic_wids over several blocks, in
    block order — THE ordering contract between an op's declared
    nested_while_ids attr, its NestedSteps outputs, and the executor's
    zip of the two. Every layer/op that wires nested trip counts goes
    through this one function."""
    wids = []
    for b in blk_idxs:
        for w in nested_dynamic_wids(program, b):
            if w not in wids:
                wids.append(w)
    return wids


def _collect_reports(ctx, trace_fn):
    """Run `trace_fn()` with a fresh nested-steps report dict in
    ctx.extra; returns (trace result, {wid: steps tracer}) reported by
    dynamic Whiles lowered inside it. The probe-and-replay WhileGrad
    measures NESTED loops this way: each level max-accumulates its
    children's per-iteration trip counts in its own carry (reference
    analog: while_op.cc:96 step scopes nest freely)."""
    extra = ctx.extra
    saved = extra.get("nested_steps_report")
    extra["nested_steps_report"] = {}
    try:
        result = trace_fn()
        rep = extra["nested_steps_report"]
    finally:
        extra["nested_steps_report"] = saved
    return result, rep


def _publish_report(ctx, entries):
    """Report {wid: steps} to an enclosing collector, if any."""
    rep = ctx.extra.get("nested_steps_report")
    if rep is not None:
        rep.update(entries)


def _zero_steps():
    return jnp.zeros((), jnp.int32)


# what a static_rnn keeps between its forward scan and its transpose.
# While one traces its sub-block (and the blocks nested in it),
# extra["kept_outputs"] is `_name_kept` over the block's operand names:
# core/executor.py _trace_ops passes every op's outputs through it
KEEPS = "products_and_kernels"

# the ops whose output is kept too where a product or an attention site
# of the body reads it: both work in float32 inside and hand on the
# program's width, and XLA fuses a recomputed one into EVERY consumer
# in the transpose (the step's executable grew by a quarter and every
# weight gradient re-ran its norm: PERF.md section 6, PR 45);
# everything else is recomputed
_KEPT_OPERANDS = frozenset({"rms_norm", "rotary_embedding"})


def _count_loop_site(ctx, passes, blk_idx):
    """One count a counted loop traced into a step program, in the
    idiom of ops/nn_ops.py _count_sdpa_site."""
    if "program" not in ctx.extra:
        return
    from ..observability.registry import default_registry
    default_registry().counter(
        "paddle_tpu_loop_sites_total",
        "Counted loops (StaticRNN(steps=T): one lax.scan over one "
        "sub-block, no step input) traced into a step program, by the "
        "passes the loop makes, the ops of its sub-block and what the "
        "scan keeps of a pass for its transpose. A grad op that "
        "replays its loop counts it again.",
        ("passes", "body_ops", "keeps")).labels(
            passes=str(passes), body_ops=str(len(
                ctx.extra["program"].blocks[blk_idx].ops)),
            keeps=KEEPS).inc()


def _operand_names(block):
    """Names the block's products and attention sites read."""
    from ..analysis.cost_model import PRODUCT_OPS
    return frozenset(
        n for op in block.ops
        if op.type in PRODUCT_OPS
        or op.type == "scaled_dot_product_attention"
        for ns in op.inputs.values() for n in ns)


def _name_kept(operands, op, outs):
    """The output of a matrix product, and of a norm or rotary
    embedding that feeds one (`_KEPT_OPERANDS`), under the name the
    loop's policy keeps, AS THE PROGRAM HOLDS IT: under AMP `mul` hands
    on its float32 accumulator rounded to bfloat16, and the rounded
    value is what is named."""
    from ..analysis.cost_model import PRODUCT_OPS
    if op.type not in PRODUCT_OPS and not (
            op.type in _KEPT_OPERANDS
            and any(n in operands for ns in op.outputs.values()
                    for n in ns)):
        return outs
    return {k: checkpoint_name(v, KEEPS) for k, v in outs.items()}


_named_kept = jax.checkpoint_policies.save_only_these_names(KEEPS)


def _kept(prim, *avals, **params):
    """The loop's one policy: a named output (`_name_kept`) and every
    output of a Pallas call (the flash forward's o and logsumexp: a
    kernel never runs again in the transpose). A named value the
    transpose does not read is not stacked: with the rotated q and k
    kept, the q and k products' outputs are not."""
    return prim.name == "pallas_call" or _named_kept(prim, *avals, **params)


@register_op_CF("static_rnn")
def _static_rnn(ctx):
    """Scan over leading time axis of each step input, or attr
    ``steps`` times over the memories alone (StaticRNN's counted form):
    one ``jax.lax.scan`` either way, the sub-block traced once. A
    memory keeps its init's dtype (under AMP a body hands a float32
    stream back at half width).

    What the scan keeps of a pass for its transpose: the carry (scan's
    own), the outputs of the body's matrix products at the width the
    program holds them, the output of a norm or a rotary embedding
    that a product or an attention site reads (`_name_kept`) and the
    outputs of its Pallas calls; the body is under ``jax.checkpoint``
    with that one policy (`_kept`), so everything else — the other
    norms, casts, relayouts, elementwise ops, activations, residual
    adds, an attention site no kernel served — is computed again in
    the transpose from what was kept. A body without a product keeps
    its carry alone. A program without a grad op of the loop is untouched:
    without a transpose ``jax.checkpoint`` is the identity."""
    xs = ctx.inputs("X")                 # each [T, ...]
    mem_init = ctx.inputs("MemInit")
    step_in = ctx.attr("step_in_names")
    mem_pre = ctx.attr("mem_pre_names")
    mem_new = ctx.attr("mem_new_names")
    out_names = ctx.attr("out_names")
    blk_idx = ctx.attr("sub_block_idx")
    outer = dict(ctx.env)
    nested = nested_dynamic_wids(ctx.extra["program"], blk_idx)
    operands = _operand_names(ctx.extra["program"].blocks[blk_idx])

    def body(state, x_t):
        carry, maxes = state

        def trace():
            env = dict(outer)
            env.update(zip(mem_pre, carry))
            env.update(zip(step_in, x_t))
            return _trace_sub(ctx, blk_idx, env)

        enclosing = ctx.extra.get("kept_outputs")
        ctx.extra["kept_outputs"] = partial(_name_kept, operands)
        try:
            env, rep = _collect_reports(ctx, trace)
        finally:
            ctx.extra["kept_outputs"] = enclosing
        maxes = tuple(jnp.maximum(m, rep.get(w, _zero_steps()))
                      for w, m in zip(nested, maxes))
        new_carry = tuple(env[n].astype(c.dtype)
                          for n, c in zip(mem_new, carry))
        outs = tuple(env[n] for n in out_names)
        return (new_carry, maxes), outs

    steps = ctx.attr("steps", None)
    if steps is not None:
        _count_loop_site(ctx, int(steps), blk_idx)
    state0 = (tuple(mem_init), tuple(_zero_steps() for _ in nested))
    # prevent_cse=False: inside a scan the barrier buys nothing and
    # costs fusions
    (_, maxes), stacked = jax.lax.scan(
        jax.checkpoint(body, policy=_kept, prevent_cse=False),
        state0, tuple(xs), length=steps)
    ctx.set_outputs("Out", list(stacked))
    ctx.set_outputs("NestedSteps", list(maxes))
    _publish_report(ctx, dict(zip(nested, maxes)))


@register_op_CF("while")
def _while(ctx):
    """While loop. Two lowerings:

    - default: lax.while_loop — dynamic trip count, minimal compute,
      but NOT reverse-differentiable (XLA has no rule for it);
    - with a positive `max_steps` attr: a bounded lax.scan that runs
      max_steps iterations with an active mask (finished state passes
      through) — same result for loops that terminate within the bound,
      and fully differentiable, the TPU-native WhileGrad
      (reference: while_op.cc:96 step-scope replay)."""
    cond_name = ctx.attr("cond_name")
    carried = ctx.attr("carried_names")
    blk_idx = ctx.attr("sub_block_idx")
    max_steps = int(ctx.attr("max_steps", 0) or 0)
    # Unbounded loop under the executor's probe-and-replay WhileGrad:
    # the executor measured this loop's trip count with a forward probe
    # and injects a (bucketed) static bound — the loop then lowers to
    # the differentiable masked scan instead of lax.while_loop
    # (reference analog: while_op.cc:96 step-scope replay).
    if max_steps <= 0:
        bounds = (ctx.extra or {}).get("while_bounds") or {}
        wid = ctx.attr("while_id")
        if wid in bounds:
            max_steps = int(bounds[wid])
    outer = dict(ctx.env)
    cond0 = ctx.input("Cond")
    init = tuple(outer[n] for n in carried)
    wid = ctx.attr("while_id")
    # dynamic Whiles nested anywhere below: their per-iteration trip
    # counts are max-accumulated through this loop's carry so the
    # executor's probe can read one static bound per nesting level
    nested = nested_dynamic_wids(ctx.extra["program"], blk_idx)

    def body_env(vals):
        env = dict(outer)
        env.update(zip(carried, vals))
        env = _trace_sub(ctx, blk_idx, env)
        return (env[cond_name].reshape(()).astype(jnp.bool_),
                tuple(env[n] for n in carried))

    def body_with_reports(vals, maxes):
        (new_cond, new_vals), rep = _collect_reports(
            ctx, lambda: body_env(vals))
        new_maxes = tuple(jnp.maximum(m, rep.get(w, _zero_steps()))
                          for w, m in zip(nested, maxes))
        return new_cond, new_vals, new_maxes

    maxes0 = tuple(_zero_steps() for _ in nested)

    if max_steps > 0:
        def scan_body(state, _):
            active, count, maxes, vals = state
            new_cond, new_vals, new_maxes = body_with_reports(vals, maxes)
            # carries may be pytrees (e.g. RaggedPair): select per leaf
            kept = tuple(
                jax.tree_util.tree_map(
                    lambda a, b: jnp.where(active, a, b), n, o)
                for n, o in zip(new_vals, vals))
            new_maxes = tuple(jnp.where(active, nm, m)
                              for nm, m in zip(new_maxes, maxes))
            count = count + active.astype(jnp.int32)
            return (active & new_cond, count, new_maxes, kept), None

        state0 = (cond0.reshape(()).astype(jnp.bool_),
                  jnp.zeros((), jnp.int32), maxes0, init)
        (still_active, count, maxes, final_vals), _ = jax.lax.scan(
            scan_body, state0, None, length=max_steps)
        ctx.set_outputs("Out", list(final_vals))
        # still true after max_steps iterations => the loop was truncated
        # (silent-truncation hazard of the bounded lowering); surfaced as
        # an optional output the layer wires to `<name>.exhausted`
        ctx.set_output("Exhausted", still_active)
        ctx.set_output("Steps", count)
        ctx.set_outputs("NestedSteps", list(maxes))
        _publish_report(ctx, dict(zip(nested, maxes)))
        return

    def cond_fn(state):
        return state[0].reshape(())

    def body_fn(state):
        maxes = state[2:2 + len(nested)]
        new_cond, new_vals, new_maxes = body_with_reports(
            state[2 + len(nested):], maxes)
        return (new_cond, state[1] + 1) + new_maxes + new_vals

    final = jax.lax.while_loop(
        cond_fn, body_fn,
        (cond0.reshape(()).astype(jnp.bool_), jnp.zeros((), jnp.int32))
        + maxes0 + init)
    steps = final[1]
    maxes = final[2:2 + len(nested)]
    ctx.set_outputs("Out", list(final[2 + len(nested):]))
    ctx.set_output("Steps", steps)
    ctx.set_outputs("NestedSteps", list(maxes))
    # visible to an enclosing collector: own trip count + children's
    _publish_report(ctx, {wid: steps, **dict(zip(nested, maxes))})


@register_op_CF("cond")
def _cond(ctx):
    pred = ctx.input("Pred")
    outer = dict(ctx.env)
    prog = ctx.extra["program"]
    tb = ctx.attr("true_block_idx")
    fb = ctx.attr("false_block_idx")
    # dynamic Whiles inside either branch report their trip counts as
    # extra lax.cond outputs — a tracer may not leak from a branch
    # trace into an enclosing collector directly (the untaken branch
    # contributes zeros, which can only under-report; the probe only
    # needs counts for what actually EXECUTED)
    wids = ctx.attr("nested_while_ids", None)
    if wids is None:   # op built without the layer: same union, same order
        wids = union_nested_wids(prog, (tb, fb))

    def make_branch(blk_idx, out_name):
        def branch(_):
            env, rep = _collect_reports(
                ctx, lambda: _trace_sub(ctx, blk_idx, dict(outer)))
            return (env[out_name],) + tuple(
                rep.get(w, _zero_steps()) for w in wids)
        return branch

    res = jax.lax.cond(pred.reshape(()).astype(jnp.bool_),
                       make_branch(tb, ctx.attr("true_out")),
                       make_branch(fb, ctx.attr("false_out")),
                       operand=None)
    ctx.set_output("Out", res[0])
    ctx.set_outputs("NestedSteps", list(res[1:]))
    _publish_report(ctx, dict(zip(wids, res[1:])))


# -- tensor arrays (dense fixed-capacity form) ------------------------------

@register_op_CF("array_write", no_grad_slots=["I"])
def _array_write(ctx):
    x = ctx.input("X")
    i = ctx.input("I").reshape(()).astype(jnp.int32)
    arr = ctx.input("Array")
    if arr is None:
        cap = ctx.attr("capacity", 128)
        arr = jnp.zeros((cap,) + tuple(x.shape), x.dtype)
    out = jax.lax.dynamic_update_index_in_dim(arr, x, i, 0)
    ctx.set_output("Out", out)


@register_op_CF("array_read", no_grad_slots=["I"])
def _array_read(ctx):
    arr = ctx.input("Array")
    i = ctx.input("I").reshape(()).astype(jnp.int32)
    ctx.set_output("Out", jax.lax.dynamic_index_in_dim(arr, i, 0,
                                                       keepdims=False))


@register_op_CF("array_length", no_grad_slots=["Array"])
def _array_length(ctx):
    arr = ctx.input("Array")
    ctx.set_output("Out", jnp.asarray(arr.shape[0], jnp.int64))


@register_op_CF("dynamic_rnn")
def _dynamic_rnn(ctx):
    """Ragged-batch RNN (reference: DynamicRNN control_flow.py:1354 +
    lod_rank_table/shrink_rnn_memory machinery). The reference shrinks
    the live batch as short sequences finish; here the batch stays dense
    [B, T, ...] and finished rows simply freeze their memory (masked
    carry) — the TPU-native equivalent of shrink_rnn_memory. Outputs are
    ragged (zero-masked past each row's length).

    Contract (as in the reference, which rejects mismatched LoD): all
    ragged step inputs share one set of lengths; the FIRST input's
    lengths drive the masking. Mismatched lengths cannot be detected
    inside the traced program and silently follow the first input."""
    from ..core.lod import RaggedPair

    xs_in = ctx.inputs("X")              # ragged step inputs
    mem_init = ctx.inputs("MemInit")
    step_in = ctx.attr("step_in_names")
    mem_pre = ctx.attr("mem_pre_names")
    mem_new = ctx.attr("mem_new_names")
    out_names = ctx.attr("out_names")
    blk_idx = ctx.attr("sub_block_idx")
    outer = dict(ctx.env)

    rags = []
    for x in xs_in:
        if isinstance(x, RaggedPair):
            rags.append(x)
        else:
            rags.append(RaggedPair(
                x, jnp.full((x.shape[0],), x.shape[1], jnp.int32)))
    lengths = rags[0].lengths
    t_max = rags[0].data.shape[1]
    # time-major step data for scan
    xs_tm = tuple(jnp.moveaxis(r.data, 1, 0) for r in rags)

    nested = nested_dynamic_wids(ctx.extra["program"], blk_idx)

    def body(state, inp):
        carry, maxes = state
        t, x_t = inp
        active = (t < lengths)           # [B]

        def trace():
            env = dict(outer)
            env.update(zip(mem_pre, carry))
            env.update(zip(step_in, x_t))
            return _trace_sub(ctx, blk_idx, env)

        env, rep = _collect_reports(ctx, trace)
        maxes = tuple(jnp.maximum(m, rep.get(w, _zero_steps()))
                      for w, m in zip(nested, maxes))
        new_carry = []
        for old, name in zip(carry, mem_new):
            new = env[name]
            m = active.reshape((-1,) + (1,) * (new.ndim - 1))
            new_carry.append(jnp.where(m, new, old))
        outs = []
        for n in out_names:
            o = env[n]
            m = active.reshape((-1,) + (1,) * (o.ndim - 1))
            outs.append(jnp.where(m, o, jnp.zeros_like(o)))
        return (tuple(new_carry), maxes), tuple(outs)

    ts = jnp.arange(t_max, dtype=jnp.int32)
    state0 = (tuple(mem_init), tuple(_zero_steps() for _ in nested))
    (final_mems, maxes), stacked = jax.lax.scan(body, state0, (ts, xs_tm))
    outs = [RaggedPair(jnp.moveaxis(s, 0, 1), lengths) for s in stacked]
    ctx.set_outputs("Out", outs)
    ctx.set_outputs("LastMem", list(final_mems))
    ctx.set_outputs("NestedSteps", list(maxes))
    _publish_report(ctx, dict(zip(nested, maxes)))


@register_op_CF("if_else")
def _if_else(ctx):
    """Row-wise two-branch select (reference: IfElse control_flow.py:1252
    over split_lod_tensor/merge_lod_tensor). The reference routes each
    row to one branch's sub-executor; dense TPU form traces BOTH
    branches over the full batch and merges rows by the condition —
    compute is duplicated but stays one fused XLA program (the standard
    accelerator trade)."""
    cond = ctx.input("Cond")
    outer = dict(ctx.env)
    true_outs = ctx.attr("true_out_names")
    false_outs = ctx.attr("false_out_names")
    prog = ctx.extra["program"]
    tb = ctx.attr("true_block_idx")
    fb = ctx.attr("false_block_idx")
    wids = ctx.attr("nested_while_ids", None)
    if wids is None:
        wids = union_nested_wids(prog, (tb, fb))

    env_t, rep_t = _collect_reports(
        ctx, lambda: _trace_sub(ctx, tb, dict(outer)))
    env_f, rep_f = _collect_reports(
        ctx, lambda: _trace_sub(ctx, fb, dict(outer)))
    c = cond.reshape(-1).astype(jnp.bool_)
    merged = []
    for tn, fn in zip(true_outs, false_outs):
        tv, fv = env_t[tn], env_f[fn]
        m = c.reshape((-1,) + (1,) * (tv.ndim - 1))
        merged.append(jnp.where(m, tv, fv))
    ctx.set_outputs("Out", merged)
    # both branches execute in the dense lowering: report the max
    maxes = tuple(jnp.maximum(rep_t.get(w, _zero_steps()),
                              rep_f.get(w, _zero_steps()))
                  for w in wids)
    ctx.set_outputs("NestedSteps", list(maxes))
    _publish_report(ctx, dict(zip(wids, maxes)))


@register_op_CF("pipeline")
def _pipeline(ctx):
    """Program-level GPipe pipeline (layers/control_flow.py
    PipelinedStack). With a mesh carrying the pipe axis: microbatched
    pipeline_apply (ppermute activation hops inside one scan, stage
    params sharded stage-per-device). Without one: sequential stage
    composition — identical math and gradients, so single-device
    Executors and the ParallelExecutor run the same program."""
    x = ctx.input("X")
    params = ctx.inputs("StageParams")
    names = ctx.attr("param_names")
    n_stages = ctx.attr("n_stages")
    n_micro = ctx.attr("n_micro", 1)
    axis = ctx.attr("axis", "pipe")
    blk_idx = ctx.attr("sub_block_idx")
    sin = ctx.attr("stage_in_name")
    sout = ctx.attr("stage_out_name")
    outer = dict(ctx.env)

    def stage_fn(pdict, a):
        env = dict(outer)
        env.update(pdict)
        env[sin] = a
        env = _trace_sub(ctx, blk_idx, env)
        return env[sout]

    mesh = ctx.extra.get("mesh")
    if mesh is not None and axis in mesh.axis_names:
        if mesh.shape[axis] != n_stages:
            raise ValueError(
                f"pipeline has n_stages={n_stages} but mesh axis "
                f"{axis!r} spans {mesh.shape[axis]} devices")
        from ..parallel.pipeline import (merge_microbatches, pipeline_apply,
                                         split_microbatches)
        micro = split_microbatches(x, n_micro)
        stacked = dict(zip(names, params))
        # combined DP x PP: if the mesh also carries a 'data' axis, keep
        # the microbatch dim sharded over it (each DP row pipelines its
        # own batch shard; GSPMD reshards replicated feeds as needed)
        batch_axis = "data" if "data" in mesh.axis_names else None
        out = pipeline_apply(stage_fn, stacked, micro, axis=axis, mesh=mesh,
                             batch_axis=batch_axis)
        out = merge_microbatches(out)
    else:
        a = x
        for i in range(n_stages):
            a = stage_fn({n: p[i] for n, p in zip(names, params)}, a)
        out = a
    ctx.set_output("Out", out)


@register_op_CF("go", stateful=True)
def _go(ctx):
    """In-graph go: launch the sub-block on a host thread when this op
    executes (reference: go_op.cc:29 — ExecuteOnThread of the sub-block
    against a child scope). Captured inputs are snapshotted through an
    ordered io_callback at the op's program position, then the body ops
    run EAGERLY (concrete jax values) on the spawned thread — so its
    channel ops interoperate with the program's own io_callback channel
    sends/recvs and with host concurrency.Channel users. Fire and
    forget: no outputs flow back (as in the reference)."""
    from ..concurrency import go as host_go
    from ..core.registry import run_op

    blk_idx = ctx.attr("sub_block_idx")
    captured = list(ctx.attr("captured_names", []) or [])
    vals = ctx.inputs("X") or []
    prog = ctx.extra["program"]
    block = prog.blocks[blk_idx]

    def _host_launch(*snap):
        import numpy as _np

        def body():
            env = {n: _np.asarray(v) for n, v in zip(captured, snap)}
            extra = {
                "program": prog,
                "step": jnp.zeros((), jnp.int32),
                "prng": lambda seed: jax.random.PRNGKey(seed),
            }
            for op in block.ops:
                env.update(run_op(op, env, extra))
        host_go(body)
        return _np.int32(1)

    status = jax.experimental.io_callback(
        _host_launch, jax.ShapeDtypeStruct((), jnp.int32), *vals,
        ordered=True)
    ctx.set_output("Status", status)


# ---------------------------------------------------------------------------
# Explicit shape-inference rules for the control-flow family.
#
# The generic build-time mechanism (framework.infer_op_outputs) abstractly
# evaluates an op's compute rule — but these ops trace their SUB-BLOCKS and
# need extra["program"] plus closure vars, so eval_shape cannot run them and
# they were the most common "no shape-inference coverage" gaps the static
# verifier found. The rules below derive output metadata structurally:
#
# - while:      Out re-writes already-declared parent carries; the
#               Exhausted/Steps/NestedSteps flags are scalars.
# - if_else:    Out[i] mirrors the true branch's i-th output var.
# - static_rnn: Out[i] = [T, *step_out_shape] (scan stacks the per-step
#               output over the leading time axis of X, or attr `steps`).
# - dynamic_rnn: Out[i] mirrors the sub-block step output (ragged,
#               lod_level 1); LastMem[i] mirrors the init memory.
#
# Rule contract (framework._infer_shapes): rule(block_desc, op) -> dict
# {name: {"shape", "dtype", "lod_level"}} filling only what the builder
# left undeclared.

def _scalar_specs(op, slots_dtypes):
    specs = {}
    for slot, dtype in slots_dtypes:
        for n in op.output(slot):
            specs[n] = {"shape": [], "dtype": dtype, "lod_level": 0}
    return specs


def _sub_var(block_desc, blk_idx, name):
    prog = block_desc.program
    if not isinstance(blk_idx, int) or not 0 <= blk_idx < len(prog.blocks):
        return None
    return prog.blocks[blk_idx].find_var_recursive(name)


def _while_infer(block_desc, op):
    return _scalar_specs(op, [("Exhausted", "bool"), ("Steps", "int32"),
                              ("NestedSteps", "int32")])


def _if_else_infer(block_desc, op):
    specs = _scalar_specs(op, [("NestedSteps", "int32")])
    tb = op.attrs.get("true_block_idx")
    for out, tn in zip(op.output("Out"),
                       op.attrs.get("true_out_names") or []):
        tv = _sub_var(block_desc, tb, tn)
        if tv is not None and tv.shape is not None:
            specs[out] = {"shape": list(tv.shape), "dtype": tv.dtype,
                          "lod_level": tv.lod_level}
    return specs


def _static_rnn_infer(block_desc, op):
    specs = _scalar_specs(op, [("NestedSteps", "int32")])
    t_dim = op.attrs.get("steps") or -1
    for xn in op.input("X"):
        xv = block_desc.find_var_recursive(xn)
        if xv is not None and xv.shape:
            t_dim = xv.shape[0]
            break
    blk_idx = op.attrs.get("sub_block_idx")
    for out, sn in zip(op.output("Out"),
                       op.attrs.get("out_names") or []):
        sv = _sub_var(block_desc, blk_idx, sn)
        if sv is not None and sv.shape is not None:
            specs[out] = {"shape": [t_dim] + list(sv.shape),
                          "dtype": sv.dtype, "lod_level": 0}
    return specs


def _dynamic_rnn_infer(block_desc, op):
    specs = _scalar_specs(op, [("NestedSteps", "int32")])
    blk_idx = op.attrs.get("sub_block_idx")
    for out, sn in zip(op.output("Out"),
                       op.attrs.get("out_names") or []):
        sv = _sub_var(block_desc, blk_idx, sn)
        if sv is not None and sv.shape is not None:
            specs[out] = {"shape": list(sv.shape), "dtype": sv.dtype,
                          "lod_level": 1}
    for out, mn in zip(op.output("LastMem"), op.input("MemInit")):
        mv = block_desc.find_var_recursive(mn)
        if mv is not None and mv.shape is not None:
            specs[out] = {"shape": list(mv.shape), "dtype": mv.dtype,
                          "lod_level": 0}
    return specs


for _t, _rule in (("while", _while_infer), ("if_else", _if_else_infer),
                  ("static_rnn", _static_rnn_infer),
                  ("dynamic_rnn", _dynamic_rnn_infer)):
    OpRegistry.get(_t).infer_shape = _rule
