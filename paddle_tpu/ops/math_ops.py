"""Math ops: elementwise (with reference broadcast semantics), matmul,
reductions, activations, comparisons, clipping, norms.

Reference parity: paddle/fluid/operators/elementwise_op_function.h (axis
broadcast), matmul_op.cc, mul_op.cc (flatten-to-2D matmul), reduce_op.cc,
activation_op.cc, clip_op.cc, softmax_op.cc, topk. All rules are pure
jax.numpy, so the MXU sees large fused matmuls and XLA fuses the rest.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..amp import amp_cast, amp_enabled
from ..core.registry import register_op
from .core_ops import jnp_dtype


def _mxu_operands(x, *ys):
    """(operands, accumulation dtype, result dtype) of an MXU product
    under AMP: bf16 operands, float32 accumulation, and a bf16 RESULT so
    activations thread end-to-end at half width (the f32->bf16 rounding
    happens in the matmul epilogue, fused)."""
    out_dtype = functools.reduce(jnp.promote_types,
                                 (a.dtype for a in (x, *ys)))
    ops = amp_cast(x, *ys)
    if all(a.dtype == jnp.bfloat16 for a in ops) and \
            out_dtype == jnp.float32:
        return ops, jnp.float32, jnp.bfloat16
    return ops, None, out_dtype


def _mxu_matmul(x, y):
    """matmul that engages the MXU in one pass under AMP
    (`_mxu_operands`)."""
    (x, y), pref, out_dtype = _mxu_operands(x, y)
    return jnp.matmul(x, y, preferred_element_type=pref).astype(out_dtype)


def _mxu_fanout(x, ys):
    """[m, d] against k [d, f] matrices, `_mxu_matmul`'s widths: the k
    products as they are, and a pullback whose input gradient is ONE
    contraction, over (k, f), of the stacked cotangents with the
    stacked matrices. Where a mesh axis shards f the k partial sums are
    then added on the shard, in the float32 accumulator, rounded once,
    and cross that axis as ONE all-reduce; left to autodiff each
    product's input gradient is all-reduced by itself and the k results
    are added after (XLA does not reassociate the sum). The stacks are
    of shards; concatenating along f instead would cross the sharded
    axis and reshard the activations. (The same contraction as the
    FORWARD, `md,kdf->kmf`, saves the same all-reduces and costs 16
    layout copies of a q-sized array a layer pair: PERF.md, PR 50.)"""
    (x, *ys), pref, out_dtype = _mxu_operands(x, *ys)

    @jax.custom_vjp
    def products(x, ys):
        return [jnp.matmul(x, y, preferred_element_type=pref
                           ).astype(out_dtype) for y in ys]

    def pullback(operands, gs):
        x, ys = operands
        dx = jnp.einsum("kmf,kdf->md", jnp.stack(gs), jnp.stack(ys),
                        preferred_element_type=pref).astype(x.dtype)
        return dx, [jnp.matmul(x.T, g, preferred_element_type=pref
                               ).astype(y.dtype) for g, y in zip(gs, ys)]

    products.defvjp(lambda x, ys: (products(x, ys), (x, ys)), pullback)
    return products(x, ys)


def _broadcast_y(x, y, axis: int):
    """Reference elementwise broadcast: align y's dims starting at `axis`
    of x (elementwise_op_function.h). axis=-1 means trailing alignment."""
    xnd, ynd = x.ndim, y.ndim
    if xnd == ynd:
        return y
    if axis == -1 or axis is None:
        axis = xnd - ynd
    shape = [1] * axis + list(y.shape) + [1] * (xnd - axis - ynd)
    return y.reshape(shape)


def _register_elementwise(name, fn):
    @register_op(name)
    def _op(ctx, _fn=fn):
        x = ctx.input("X")
        y = ctx.input("Y")
        y = _broadcast_y(x, y, ctx.attr("axis", -1))
        # Under AMP, bf16 wins mixed bf16/f32 elementwise ops (a f32
        # bias/scale param would otherwise silently promote the whole
        # activation stream back to f32 width).
        if amp_enabled() and {getattr(x, "dtype", None),
                              getattr(y, "dtype", None)} == \
                {jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)}:
            x, y = (a.astype(jnp.bfloat16) for a in (x, y))
        ctx.set_output("Out", _fn(x, y))


_register_elementwise("elementwise_add", lambda x, y: x + y)
_register_elementwise("elementwise_sub", lambda x, y: x - y)
_register_elementwise("elementwise_mul", lambda x, y: x * y)
_register_elementwise("elementwise_div", lambda x, y: x / y)
_register_elementwise("elementwise_pow", lambda x, y: jnp.power(x, y))
_register_elementwise("elementwise_max", jnp.maximum)
_register_elementwise("elementwise_min", jnp.minimum)
_register_elementwise("elementwise_mod", jnp.mod)
_register_elementwise("elementwise_floordiv", jnp.floor_divide)


@register_op("mul")
def _mul(ctx):
    """The reference's `mul` op: flatten X to 2-D at x_num_col_dims, Y at
    y_num_col_dims, matmul, restore shape (mul_op.cc)."""
    x = ctx.input("X")
    y = ctx.input("Y")
    xn = ctx.attr("x_num_col_dims", 1)
    yn = ctx.attr("y_num_col_dims", 1)
    x2 = x.reshape((_prod(x.shape[:xn]), _prod(x.shape[xn:])))
    y2 = y.reshape((_prod(y.shape[:yn]), _prod(y.shape[yn:])))
    out = _mxu_matmul(x2, y2)
    out_shape = tuple(x.shape[:xn]) + tuple(y.shape[yn:])
    ctx.set_output("Out", out.reshape(out_shape))


def _prod(dims):
    p = 1
    for d in dims:
        p *= int(d)
    return p


def _column_sharded(ctx, names):
    """Does the mesh this step is traced for shard the OUTPUT features of
    every weight in `names` over one and the same axis? Read from what
    ParallelExecutor hands the trace: its mesh and the PartitionSpecs of
    the state (extra["state_specs"]). No mesh, a replicated weight, or a
    value under another name than its parameter's: no."""
    mesh, specs = ctx.extra.get("mesh"), ctx.extra.get("state_specs")
    if mesh is None or not specs:
        return False

    def column_axes(spec):
        ax = tuple(spec)[1] if spec is not None and len(spec) > 1 else None
        return (ax,) if isinstance(ax, str) else tuple(ax or ())

    axes = {column_axes(specs.get(n)) for n in names}
    return len(axes) == 1 and _prod(mesh.shape[a] for a in axes.pop()) > 1


def _count_fanout_site(ctx, products, path):
    """One count a fan-out site traced into a step program, in the idiom
    of ops/nn_ops.py _count_sdpa_site (the build-time shape inference
    carries no program and is no site)."""
    if "program" not in ctx.extra:
        return
    from ..observability.registry import default_registry
    default_registry().counter(
        "paddle_tpu_fanout_sites_total",
        "fanout_mul sites (several weight matrices against ONE "
        "activation: q, k, v of a self-attention, k, v of a "
        "cross-attention) traced into a step program, by the number of "
        "products and the path taken: stacked (one contraction over the "
        "stacked weights, under a mesh that shards their output "
        "features: the input gradients reach that axis as one "
        "all-reduce) or separate (one matmul a weight, as `mul`). A grad "
        "site that replays its forward rule counts again.",
        ("products", "path")).labels(
            products=str(products), path=path).inc()


@register_op("fanout_mul")
def _fanout_mul(ctx):
    """`mul` of ONE X against k matrices Y of one shape, k Out: X
    flattened to 2-D at x_num_col_dims, one product a matrix, shapes
    restored. Two branches of one rule, chosen by what the trace
    observes (`_column_sharded`): separate matmuls, or `_mxu_fanout`."""
    x = ctx.input("X")
    ys = ctx.inputs("Y")
    xn = ctx.attr("x_num_col_dims", 1)
    lead = tuple(x.shape[:xn])
    x2 = x.reshape((_prod(lead), _prod(x.shape[xn:])))
    stacked = len({y.shape for y in ys}) == 1 and ys[0].ndim == 2 and \
        _column_sharded(ctx, ctx.op.input("Y"))
    _count_fanout_site(ctx, len(ys), "stacked" if stacked else "separate")
    outs = _mxu_fanout(x2, ys) if stacked else \
        [_mxu_matmul(x2, y) for y in ys]
    ctx.set_outputs("Out", [o.reshape(lead + tuple(y.shape[1:]))
                            for o, y in zip(outs, ys)])


@register_op("matmul")
def _matmul(ctx):
    x = ctx.input("X")
    y = ctx.input("Y")
    if ctx.attr("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if ctx.attr("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    wide = ctx.attr("out_dtype", None)
    # attr out_dtype: accumulated to and returned at that width
    out = _mxu_matmul(x, y) if wide is None else jnp.matmul(
        x, y, preferred_element_type=jnp_dtype(wide))
    alpha = ctx.attr("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    ctx.set_output("Out", out)


@register_op("dot")
def _dot(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    ctx.set_output("Out", jnp.sum(x * y, axis=-1, keepdims=True))


# -- reductions -------------------------------------------------------------

def _register_reduce(name, fn):
    @register_op(name)
    def _op(ctx, _fn=fn):
        x = ctx.input("X")
        dim = ctx.attr("dim", None)
        keep = ctx.attr("keep_dim", False)
        if ctx.attr("reduce_all", False) or dim is None:
            axis = None
        else:
            axis = tuple(dim) if isinstance(dim, (list, tuple)) else (dim,)
        out = _fn(x, axis=axis, keepdims=keep)
        if axis is None and not keep:
            out = out.reshape(())
        ctx.set_output("Out", out)


_register_reduce("reduce_sum", jnp.sum)
_register_reduce("reduce_mean", jnp.mean)
_register_reduce("reduce_max", jnp.max)
_register_reduce("reduce_min", jnp.min)
_register_reduce("reduce_prod", jnp.prod)


@register_op("mean")
def _mean(ctx):
    ctx.set_output("Out", jnp.mean(ctx.input("X")))


# -- activations ------------------------------------------------------------

def _register_act(name, fn):
    @register_op(name)
    def _op(ctx, _fn=fn):
        ctx.set_output("Out", _fn(ctx.input("X")))


_register_act("relu", jax.nn.relu)
_register_act("relu6", lambda x: jnp.clip(x, 0.0, 6.0))
_register_act("sigmoid", jax.nn.sigmoid)
_register_act("logsigmoid", jax.nn.log_sigmoid)
_register_act("tanh", jnp.tanh)
_register_act("tanh_shrink", lambda x: x - jnp.tanh(x))
_register_act("softsign", lambda x: x / (1 + jnp.abs(x)))
_register_act("sqrt", jnp.sqrt)
_register_act("rsqrt", jax.lax.rsqrt)
_register_act("abs", jnp.abs)
_register_act("ceil", jnp.ceil)
_register_act("floor", jnp.floor)
_register_act("round", jnp.round)
_register_act("reciprocal", lambda x: 1.0 / x)
_register_act("square", jnp.square)
_register_act("exp", jnp.exp)
_register_act("log", jnp.log)
_register_act("gelu", jax.nn.gelu)
_register_act("sin", jnp.sin)
_register_act("cos", jnp.cos)
_register_act("sign", jnp.sign)


@register_op("softplus")
def _softplus(ctx):
    ctx.set_output("Out", jax.nn.softplus(ctx.input("X")))


@register_op("leaky_relu")
def _leaky_relu(ctx):
    alpha = ctx.attr("alpha", 0.02)
    x = ctx.input("X")
    ctx.set_output("Out", jnp.where(x >= 0, x, alpha * x))


@register_op("elu")
def _elu(ctx):
    ctx.set_output("Out", jax.nn.elu(ctx.input("X"), ctx.attr("alpha", 1.0)))


@register_op("pow")
def _pow(ctx):
    ctx.set_output("Out", jnp.power(ctx.input("X"), ctx.attr("factor", 1.0)))


@register_op("hard_sigmoid")
def _hard_sigmoid(ctx):
    slope = ctx.attr("slope", 0.2)
    offset = ctx.attr("offset", 0.5)
    ctx.set_output("Out", jnp.clip(slope * ctx.input("X") + offset, 0.0, 1.0))


@register_op("swish")
def _swish(ctx):
    beta = ctx.attr("beta", 1.0)
    x = ctx.input("X")
    ctx.set_output("Out", x * jax.nn.sigmoid(beta * x))


@register_op("soft_relu")
def _soft_relu(ctx):
    t = ctx.attr("threshold", 40.0)
    x = jnp.clip(ctx.input("X"), -t, t)
    ctx.set_output("Out", jnp.log(1 + jnp.exp(x)))


@register_op("clip")
def _clip(ctx):
    ctx.set_output("Out", jnp.clip(ctx.input("X"), ctx.attr("min", -1.0),
                                   ctx.attr("max", 1.0)))


@register_op("clip_by_norm")
def _clip_by_norm(ctx):
    x = ctx.input("X")
    max_norm = ctx.attr("max_norm", 1.0)
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    scale = jnp.minimum(max_norm / jnp.maximum(norm, 1e-12), 1.0)
    ctx.set_output("Out", x * scale)


@register_op("squared_l2_norm")
def _squared_l2_norm(ctx):
    ctx.set_output("Out", jnp.sum(jnp.square(ctx.input("X"))).reshape(()))


@register_op("l2_normalize")
def _l2_normalize(ctx):
    x = ctx.input("X")
    axis = ctx.attr("axis", -1)
    eps = ctx.attr("epsilon", 1e-12)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    ctx.set_output("Out", x / jnp.maximum(norm, eps))
    ctx.set_output("Norm", norm)


# -- softmax family ---------------------------------------------------------

@register_op("softmax")
def _softmax(ctx):
    x = ctx.input("X")
    xf = x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x
    out = jax.nn.softmax(xf, axis=ctx.attr("axis", -1))
    ctx.set_output("Out", out.astype(x.dtype))


@register_op("log_softmax")
def _log_softmax(ctx):
    x = ctx.input("X")
    xf = x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x
    out = jax.nn.log_softmax(xf, axis=ctx.attr("axis", -1))
    ctx.set_output("Out", out.astype(x.dtype))


# -- comparisons / logical --------------------------------------------------

def _register_cmp(name, fn):
    @register_op(name, no_grad_slots=["X", "Y"])
    def _op(ctx, _fn=fn):
        x, y = ctx.input("X"), ctx.input("Y")
        if y is not None:
            y = _broadcast_y(x, y, ctx.attr("axis", -1))
        ctx.set_output("Out", _fn(x, y))


_register_cmp("equal", lambda x, y: x == y)
_register_cmp("not_equal", lambda x, y: x != y)
_register_cmp("less_than", lambda x, y: x < y)
_register_cmp("less_equal", lambda x, y: x <= y)
_register_cmp("greater_than", lambda x, y: x > y)
_register_cmp("greater_equal", lambda x, y: x >= y)

_register_cmp("logical_and", jnp.logical_and)
_register_cmp("logical_or", jnp.logical_or)
_register_cmp("logical_xor", jnp.logical_xor)


@register_op("logical_not", no_grad_slots=["X"])
def _logical_not(ctx):
    ctx.set_output("Out", jnp.logical_not(ctx.input("X")))


@register_op("isfinite", no_grad_slots=["X"])
def _isfinite(ctx):
    ctx.set_output("Out", jnp.all(jnp.isfinite(ctx.input("X"))).reshape(()))


# -- misc math --------------------------------------------------------------

@register_op("top_k", no_grad_slots=["X"])
def _top_k(ctx):
    x = ctx.input("X")
    k = ctx.attr("k", 1)
    vals, idxs = jax.lax.top_k(x, k)
    ctx.set_output("Out", vals)
    ctx.set_output("Indices", idxs.astype(jnp.int64))


@register_op("arg_max", no_grad_slots=["X"])
def _arg_max(ctx):
    ctx.set_output("Out", jnp.argmax(ctx.input("X"),
                                     axis=ctx.attr("axis", -1)).astype(jnp.int64))


@register_op("arg_min", no_grad_slots=["X"])
def _arg_min(ctx):
    ctx.set_output("Out", jnp.argmin(ctx.input("X"),
                                     axis=ctx.attr("axis", -1)).astype(jnp.int64))


@register_op("cumsum")
def _cumsum(ctx):
    x = ctx.input("X")
    axis = ctx.attr("axis", -1)
    reverse = ctx.attr("reverse", False)
    exclusive = ctx.attr("exclusive", False)
    work = jnp.flip(x, axis) if reverse else x
    out = jnp.cumsum(work, axis=axis)
    if exclusive:
        # shift forward along axis: out[i] = sum of strictly-earlier elems
        pad = [(0, 0)] * x.ndim
        pad[axis % x.ndim] = (1, 0)
        out = jnp.pad(out, pad)[tuple(
            slice(0, s) if i == (axis % x.ndim) else slice(None)
            for i, s in enumerate(x.shape))]
    if reverse:
        out = jnp.flip(out, axis)
    ctx.set_output("Out", out)


@register_op("maxout")
def _maxout(ctx):
    x = ctx.input("X")  # NCHW
    groups = ctx.attr("groups")
    n, c, h, w = x.shape
    ctx.set_output("Out", x.reshape(n, c // groups, groups, h, w).max(axis=2))


@register_op("cos_sim")
def _cos_sim(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    xn = jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(jnp.square(y), -1, keepdims=True))
    out = jnp.sum(x * y, -1, keepdims=True) / (xn * yn + 1e-12)
    ctx.set_output("Out", out)
    ctx.set_output("XNorm", xn)
    ctx.set_output("YNorm", yn)


# -- remaining activation surface (reference: activation_op.cc) -------------

_register_act("stanh", lambda x: 1.7159 * jnp.tanh(0.66667 * x))


@register_op("brelu")
def _brelu(ctx):
    x = ctx.input("X")
    t_min = ctx.attr("t_min", 0.0)
    t_max = ctx.attr("t_max", 24.0)
    ctx.set_output("Out", jnp.clip(x, t_min, t_max))


@register_op("hard_shrink")
def _hard_shrink(ctx):
    x = ctx.input("X")
    t = ctx.attr("threshold", 0.5)
    ctx.set_output("Out", jnp.where(jnp.abs(x) > t, x, 0.0))


@register_op("softshrink")
def _softshrink(ctx):
    x = ctx.input("X")
    lam = ctx.attr("lambda", 0.5)
    ctx.set_output("Out", jnp.where(x > lam, x - lam,
                                    jnp.where(x < -lam, x + lam, 0.0)))


@register_op("thresholded_relu")
def _thresholded_relu(ctx):
    x = ctx.input("X")
    t = ctx.attr("threshold", 1.0)
    ctx.set_output("Out", jnp.where(x > t, x, 0.0))


@register_op("prelu")
def _prelu(ctx):
    """PReLU with learned slope (reference: prelu_op.cc — 'all' mode
    shares one alpha; 'channel' mode one per channel dim 1)."""
    x = ctx.input("X")
    alpha = ctx.input("Alpha")
    mode = ctx.attr("mode", "all")
    if mode == "channel" and x.ndim >= 2:
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    else:
        alpha = alpha.reshape((1,) * x.ndim)
    ctx.set_output("Out", jnp.where(x > 0, x, alpha * x))


@register_op("label_smooth", no_grad_slots=["PriorDist"])
def _label_smooth(ctx):
    """(1-eps)*label + eps*prior (uniform when no prior);
    reference: label_smooth_op.cc."""
    x = ctx.input("X")
    eps = ctx.attr("epsilon", 0.0)
    prior = ctx.input("PriorDist")
    if prior is None:
        prior = 1.0 / x.shape[-1]
    ctx.set_output("Out", (1.0 - eps) * x + eps * prior)


# -- remaining losses (reference: *_loss_op.cc) -----------------------------

@register_op("modified_huber_loss", no_grad_slots=["Y"])
def _modified_huber_loss(ctx):
    """Classification Huber loss on y in {0,1} (reference:
    modified_huber_loss_op.cc): z = 2y-1; yv = z*pred;
    loss = (1-yv)^2 clipped quadratic for yv >= -1 else -4*yv."""
    x = ctx.input("X")
    y = ctx.input("Y").astype(x.dtype)
    yv = (2.0 * y - 1.0) * x
    loss = jnp.where(yv < -1.0, -4.0 * yv,
                     jnp.square(jnp.maximum(0.0, 1.0 - yv)))
    ctx.set_output("IntermediateVal", yv)
    ctx.set_output("Out", loss)


@register_op("rank_loss", no_grad_slots=["Label"])
def _rank_loss(ctx):
    """Pairwise ranking loss (reference: rank_loss_op.cc):
    C = -label*(l-r) + log(1+exp(l-r))."""
    label = ctx.input("Label")
    left = ctx.input("Left")
    right = ctx.input("Right")
    d = left - right
    ctx.set_output("Out", -label * d + jnp.logaddexp(0.0, d))


@register_op("squared_l2_distance")
def _squared_l2_distance(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    diff = x - y.reshape(y.shape if y.shape[0] == x.shape[0]
                         else (1,) + tuple(y.shape[1:]))
    ctx.set_output("sub_result", diff)
    ctx.set_output("Out", jnp.sum(jnp.square(diff), axis=-1, keepdims=True))


@register_op("l1_norm")
def _l1_norm(ctx):
    ctx.set_output("Out", jnp.sum(jnp.abs(ctx.input("X"))))


@register_op("norm")
def _norm(ctx):
    """L2-normalize along channel dim 1 with learned scale (reference:
    norm_op.cc — out = scale_c * x / ||x||_2 over channels)."""
    x = ctx.input("X")
    scale = ctx.input("Scale")
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True) + 1e-10)
    scale = scale.reshape((1, -1) + (1,) * (x.ndim - 2))
    ctx.set_output("Out", scale * x / norm)


# -- misc parity ops --------------------------------------------------------

@register_op("bilinear_tensor_product")
def _bilinear_tensor_product(ctx):
    """out[:, k] = x @ W_k @ y^T diag + bias (reference:
    bilinear_tensor_product_op.cc)."""
    x = ctx.input("X")          # [n, dx]
    y = ctx.input("Y")          # [n, dy]
    w = ctx.input("Weight")     # [k, dx, dy]
    out = jnp.einsum("nd,kde,ne->nk", x, w, y)
    bias = ctx.input("Bias")
    if bias is not None:
        out = out + bias.reshape(1, -1)
    ctx.set_output("Out", out)


@register_op("conv_shift")
def _conv_shift(ctx):
    """Circular 1-D correlation (reference: conv_shift_op.cc): out[i,j] =
    sum_k x[i, (j+k-m//2) mod n] * y[i,k] with y width m (odd)."""
    x = ctx.input("X")  # [b, n]
    y = ctx.input("Y")  # [b, m], m odd, m <= n
    b, n = x.shape
    m = y.shape[1]
    half = m // 2
    idx = (jnp.arange(n)[:, None] + jnp.arange(m)[None, :] - half) % n
    ctx.set_output("Out", jnp.einsum("bnm,bm->bn", x[:, idx], y))


@register_op("is_empty", no_grad_slots=["X"])
def _is_empty(ctx):
    import numpy as _np
    x = ctx.input("X")
    size = int(_np.prod(x.shape)) if x.shape else 0
    ctx.set_output("Out", jnp.asarray(size == 0))


@register_op("minus")
def _minus(ctx):
    """Out = X - Y (reference: minus_op.cc)."""
    ctx.set_output("Out", ctx.input("X") - ctx.input("Y"))
