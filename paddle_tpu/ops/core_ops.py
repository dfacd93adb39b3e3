"""Core ops: feed/fetch, constants, random init, sum, cast, and the generic
vjp-based grad op that powers desc-level autodiff.

Reference parity: fill_constant/uniform_random/gaussian_random ops
(paddle/fluid/operators/fill_constant_op.cc, uniform_random_op.cc,
gaussian_random_op.cc), sum_op.cc, cast_op.cc, scale_op.cc, assign_op.cc.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.ir import OpDesc
from ..core.lod import RaggedNested, RaggedPair, RaggedTree
from ..core.registry import ExecutionContext, register_op

_JNP_DTYPE = {
    "float32": jnp.float32, "float64": jnp.float64, "float16": jnp.float16,
    "bfloat16": jnp.bfloat16, "int8": jnp.int8, "int16": jnp.int16,
    "int32": jnp.int32, "int64": jnp.int64, "uint8": jnp.uint8,
    "bool": jnp.bool_,
}


def jnp_dtype(name: str):
    return _JNP_DTYPE[name]


# -- plumbing ---------------------------------------------------------------

@register_op("feed")
def _feed(ctx):
    # Feeding is handled by the Executor before tracing; kept for IR parity
    # with the reference's feed_op (feed_fetch_method.cc).
    x = ctx.input("X")
    if x is not None:
        ctx.set_output("Out", x)


@register_op("fetch")
def _fetch(ctx):
    x = ctx.input("X")
    if x is not None:
        ctx.set_output("Out", x)


@register_op("assign")
def _assign(ctx):
    ctx.set_output("Out", ctx.input("X"))


@register_op("share_data")
def _share_data(ctx):
    ctx.set_output("Out", ctx.input("X"))


@register_op("print")
def _print(ctx):
    # Debug printing inside a jitted graph (reference: print_op.cc).
    x = ctx.input("X")
    jax.debug.print(ctx.attr("message", "print_op") + ": {}", x)
    ctx.set_output("Out", x)


# -- constants / random -----------------------------------------------------

@register_op("fill_constant")
def _fill_constant(ctx):
    shape = ctx.attr("shape")
    dtype = jnp_dtype(ctx.attr("dtype", "float32"))
    value = ctx.attr("value", 0.0)
    ctx.set_output("Out", jnp.full(shape, value, dtype=dtype))


@register_op("fill_constant_like")
def _fill_constant_like(ctx):
    x = ctx.input("X")
    value = ctx.attr("value", 0.0)
    ctx.set_output("Out", jnp.full(jnp.shape(x), value, dtype=x.dtype))


@register_op("fill_constant_batch_size_like", no_grad_slots=["Input"])
def _fill_constant_batch_size_like(ctx):
    x = ctx.input("Input")
    shape = list(ctx.attr("shape"))
    shape[ctx.attr("output_dim_idx", 0)] = x.shape[ctx.attr("input_dim_idx", 0)]
    dtype = jnp_dtype(ctx.attr("dtype", "float32"))
    ctx.set_output("Out", jnp.full(shape, ctx.attr("value", 0.0), dtype=dtype))


@register_op("fill_zeros_like")
def _fill_zeros_like(ctx):
    ctx.set_output("Out", jnp.zeros_like(ctx.input("X")))


def _op_key(ctx):
    """Deterministic PRNG key for a random op: seed attr folded with step."""
    seed = ctx.attr("seed", 0) or 0
    prng = ctx.extra.get("prng")
    if prng is None:
        return jax.random.PRNGKey(seed)
    return prng(seed)


@register_op("uniform_random")
def _uniform_random(ctx):
    shape = ctx.attr("shape")
    dtype = jnp_dtype(ctx.attr("dtype", "float32"))
    lo, hi = ctx.attr("min", -1.0), ctx.attr("max", 1.0)
    out = jax.random.uniform(_op_key(ctx), tuple(shape), dtype=jnp.float32,
                             minval=lo, maxval=hi).astype(dtype)
    ctx.set_output("Out", out)


@register_op("gaussian_random")
def _gaussian_random(ctx):
    shape = ctx.attr("shape")
    dtype = jnp_dtype(ctx.attr("dtype", "float32"))
    mean, std = ctx.attr("mean", 0.0), ctx.attr("std", 1.0)
    out = mean + std * jax.random.normal(_op_key(ctx), tuple(shape),
                                         dtype=jnp.float32)
    ctx.set_output("Out", out.astype(dtype))


@register_op("truncated_gaussian_random")
def _truncated_gaussian_random(ctx):
    shape = ctx.attr("shape")
    dtype = jnp_dtype(ctx.attr("dtype", "float32"))
    mean, std = ctx.attr("mean", 0.0), ctx.attr("std", 1.0)
    out = mean + std * jax.random.truncated_normal(
        _op_key(ctx), -2.0, 2.0, tuple(shape), dtype=jnp.float32)
    ctx.set_output("Out", out.astype(dtype))


@register_op("assign_value")
def _assign_value(ctx):
    import numpy as _np
    shape = ctx.attr("shape")
    dtype = jnp_dtype(ctx.attr("dtype", "float32"))
    vals = _np.asarray(ctx.attr("values"), dtype=dtype).reshape(shape)
    ctx.set_output("Out", jnp.asarray(vals))


@register_op("fill")
def _fill(ctx):
    """Fill Out with the literal `value` list (reference: fill_op.cc)."""
    import numpy as _np
    shape = ctx.attr("shape")
    dtype = jnp_dtype(ctx.attr("dtype", "float32"))
    vals = _np.asarray(ctx.attr("value"), dtype=dtype).reshape(shape)
    ctx.set_output("Out", jnp.asarray(vals))


def _batch_size_like_shape(ctx):
    """Output shape = attr `shape` with the batch dim taken from Input
    (reference: batch_size_like.h)."""
    shape = list(ctx.attr("shape"))
    in_idx = ctx.attr("input_dim_idx", 0)
    out_idx = ctx.attr("output_dim_idx", 0)
    shape[out_idx] = ctx.input("Input").shape[in_idx]
    return tuple(shape)


@register_op("uniform_random_batch_size_like", no_grad_slots=["Input"])
def _uniform_random_batch_size_like(ctx):
    """reference: uniform_random_batch_size_like_op.cc"""
    dtype = jnp_dtype(ctx.attr("dtype", "float32"))
    lo, hi = ctx.attr("min", -1.0), ctx.attr("max", 1.0)
    out = jax.random.uniform(_op_key(ctx), _batch_size_like_shape(ctx),
                             dtype=jnp.float32, minval=lo, maxval=hi)
    ctx.set_output("Out", out.astype(dtype))


@register_op("gaussian_random_batch_size_like", no_grad_slots=["Input"])
def _gaussian_random_batch_size_like(ctx):
    """reference: gaussian_random_batch_size_like_op.cc"""
    dtype = jnp_dtype(ctx.attr("dtype", "float32"))
    mean, std = ctx.attr("mean", 0.0), ctx.attr("std", 1.0)
    out = mean + std * jax.random.normal(
        _op_key(ctx), _batch_size_like_shape(ctx), dtype=jnp.float32)
    ctx.set_output("Out", out.astype(dtype))


@register_op("randint")
def _randint(ctx):
    shape = ctx.attr("shape")
    dtype = jnp_dtype(ctx.attr("dtype", "int64"))
    out = jax.random.randint(_op_key(ctx), tuple(shape), ctx.attr("low", 0),
                             ctx.attr("high", 100), dtype=dtype)
    ctx.set_output("Out", out)


# -- basic transforms -------------------------------------------------------

@register_op("sum")
def _sum(ctx):
    xs = ctx.inputs("X")
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    ctx.set_output("Out", out)


@register_op("cast")
def _cast(ctx):
    x = ctx.input("X")
    ctx.set_output("Out", x.astype(jnp_dtype(ctx.attr("out_dtype", "float32"))))


@register_op("scale")
def _scale(ctx):
    x = ctx.input("X")
    scale = ctx.attr("scale", 1.0)
    bias = ctx.attr("bias", 0.0)
    if ctx.attr("bias_after_scale", True):
        ctx.set_output("Out", x * scale + bias)
    else:
        ctx.set_output("Out", (x + bias) * scale)


@register_op("increment")
def _increment(ctx):
    x = ctx.input("X")
    # keep the carry dtype stable (int counters must stay int inside
    # while loops)
    ctx.set_output("Out", x + jnp.asarray(ctx.attr("step", 1.0), x.dtype))


@register_op("shape")
def _shape(ctx):
    ctx.set_output("Out", jnp.asarray(jnp.shape(ctx.input("X")),
                                      dtype=jnp.int64))


# -- the generic grad op ----------------------------------------------------

_RAGGED = (RaggedPair, RaggedNested, RaggedTree)
# extra[VJP_PULLBACKS]: {id(__vjp__ op): (pullback, FwdIn values)} of the
# trace_block call that is running; that call owns the dict and removes it
VJP_PULLBACKS = "vjp_pullbacks"


def _dense(v):
    return v.data if isinstance(v, _RAGGED) else v


def _grad_out_names(fwd, out_has_grad):
    return [n for n, h in zip(fwd.output_names(), out_has_grad) if h]


def _forward_fn(fwd, replay_names, grad_out_names, env, extra,
                keep_outs=False):
    """The function a __vjp__ site differentiates: `fwd`'s rule with
    `vals` bound to `replay_names`. Only grad-receiving outputs go through
    vjp (others contribute nothing), and ragged values pass as their dense
    data (lengths are non-diff ints). With `keep_outs`, every output rides
    along as aux."""
    from ..core.registry import run_op

    def f(vals):
        local = dict(env)
        local.update(zip(replay_names, vals))
        outs = run_op(fwd, local, extra)
        res = tuple(_dense(outs[n]) for n in grad_out_names)
        return (res, outs) if keep_outs else res
    return f


def run_op_keeping_pullback(op, gop, env, extra):
    """The forward half of a __vjp__ site (core/executor.py trace_block):
    run forward op `op` ONCE, under jax.vjp with the function its grad op
    `gop` would replay, and leave the pullback with the values it was
    linearised at in extra[VJP_PULLBACKS] for `gop` to pop. Returns the
    op's outputs, or None (run it plainly) where an input is not in env
    or `op` is not the op `gop` embeds: same wiring, but an attr of the
    embedded copy has another value here (a name can be written twice;
    markers stamped on `op` alone are fine)."""
    replay_names = gop.inputs["FwdIn"]
    try:
        in_vals = tuple(env[n] for n in replay_names)
    except KeyError:
        return None
    embedded = gop.attrs["fwd_op"]["attrs"]
    if embedded is not op.attrs:
        try:
            if not all(k in op.attrs and bool(op.attrs[k] == v)
                       for k, v in embedded.items()):
                return None
        except ValueError:   # an array-valued attr: no plain truth value
            return None
    f = _forward_fn(op, replay_names,
                    _grad_out_names(op, gop.attrs["out_has_grad"]),
                    env, extra, keep_outs=True)
    _, pullback, outs = jax.vjp(f, in_vals, has_aux=True)
    extra[VJP_PULLBACKS][id(gop)] = (pullback, in_vals)
    return outs


def _count_grad_site(served, fwd_type):
    from ..observability.registry import default_registry
    default_registry().counter(
        "paddle_tpu_grad_sites_total",
        "Grad sites (__vjp__ ops) traced, by forward op type and by how "
        "they were served: reused (the pullback the forward op's own "
        "trace made) or replayed (the forward rule traced a second time "
        "under jax.vjp).", ("served", "op")).labels(
            served=served, op=fwd_type).inc()


@register_op("__vjp__", ragged_aware=True)
def _vjp(ctx):
    """Gradient of an arbitrary forward op via jax.vjp on its compute rule.

    See core/backward.py for how this op is constructed. Where the
    trace_block call that runs this op kept the forward op's pullback
    (run_op_keeping_pullback) AND every FwdIn value is the very object
    that pullback was linearised at, the pullback is applied and the
    forward rule is not run again. Otherwise (a @PRE. snapshot, a value
    renamed or dropped in between, a forward op in another block) the
    rule is replayed under jax.vjp: XLA's CSE merges replayed HLO with
    the forward op's, but NOT a custom call — a Pallas kernel in a
    replayed rule runs twice a step.
    """
    fwd = OpDesc.from_dict(ctx.attr("fwd_op"))
    in_vals = ctx.inputs("FwdIn")
    out_grads = ctx.inputs("OutGrad")
    in_need_grad = ctx.attr("in_need_grad")

    kept = (ctx.extra.get(VJP_PULLBACKS) or {}).pop(id(ctx.op), None)
    if kept is not None and all(a is b for a, b in zip(kept[1], in_vals)):
        vjp_fn = kept[0]
        _count_grad_site("reused", fwd.type)
    else:
        # Sub-block ops read outer vars via closure (see backward.py
        # _sub_block_free_vars); those ride along as extra FwdIn entries
        # so jax.vjp sees them as arguments and produces their gradients.
        closure_names = ctx.attr("closure_names", []) or []
        f = _forward_fn(fwd, fwd.input_names() + list(closure_names),
                        _grad_out_names(fwd, ctx.attr("out_has_grad")),
                        ctx.env, ctx.extra)
        _, vjp_fn = jax.vjp(f, tuple(in_vals))
        _count_grad_site("replayed", fwd.type)
    (in_grads,) = vjp_fn(tuple(_dense(g) for g in out_grads))

    idx = 0
    for need, g, v in zip(in_need_grad, in_grads, in_vals):
        if not need:
            continue
        if isinstance(g, RaggedPair):
            g = RaggedPair(g.data, v.lengths)
        elif isinstance(g, RaggedNested):
            g = RaggedNested(g.data, v.sub_lengths, v.tok_lengths)
        elif isinstance(g, RaggedTree):
            g = RaggedTree(g.data, v.lengths)
        ctx.set_output("InGrad", g, index=idx)
        idx += 1
