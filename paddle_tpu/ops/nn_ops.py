"""Neural-net ops: conv, pool, normalization, dropout, losses, embeddings.

Reference parity: paddle/fluid/operators/{conv_op.cc, pool_op.cc,
batch_norm_op.cc, layer_norm_op.cc, dropout_op.cc, cross_entropy_op.cc,
softmax_with_cross_entropy_op.cc, lookup_table_op.cc, one_hot_op.cc,
smooth_l1_loss_op.cc, huber_loss_op.cc, hinge_loss_op.cc, nce_op.cc...}.
Layout follows the reference's NCHW API; XLA's layout assignment re-tiles
for the MXU internally, so parity costs nothing on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..amp import amp_cast
from ..core.registry import register_op
from .core_ops import jnp_dtype, _op_key


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v), int(v))


# -- convolution ------------------------------------------------------------

def _conv2d_impl(x, w, strides, paddings, dilations, groups):
    # A strided 1x1 conv only READS the subsampled grid: slicing first
    # and convolving stride-1 is the same math, but its transpose
    # (weight/input grads) lowers to clean MXU matmuls + a pad, where
    # the strided form's gradients lowered to ~0.5ms/conv loop fusions
    # (copy_subtract in the device trace — the round-2 "stride-2
    # gradient fringe"). ResNet's downsample shortcuts hit this.
    if (tuple(w.shape[2:]) == (1, 1) and tuple(paddings) == (0, 0)
            and (strides[0] > 1 or strides[1] > 1) and groups == 1):
        x = x[:, :, ::strides[0], ::strides[1]]
        strides = (1, 1)
    # Under AMP both operands drop to bf16 and the OUTPUT STAYS bf16:
    # activations thread end-to-end at half width so every inter-op HBM
    # buffer halves. (Round 1 cast each op's result back to f32; device
    # traces showed the resulting convert_element_type fusions plus the
    # doubled f32 traffic dominating the HBM-bound step. The MXU
    # accumulates in f32 internally either way;
    # preferred_element_type=f32's conv transpose rule rejects
    # mixed-dtype cotangents, so full-bf16 it is.)
    x, w = amp_cast(x, w)
    return jax.lax.conv_general_dilated(
        x, w,
        window_strides=strides,
        padding=[(paddings[0], paddings[0]), (paddings[1], paddings[1])],
        rhs_dilation=dilations,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
    )


@register_op("conv2d")
def _conv2d(ctx):
    x = ctx.input("Input")
    w = ctx.input("Filter")
    out = _conv2d_impl(x, w, _pair(ctx.attr("strides", [1, 1])),
                       _pair(ctx.attr("paddings", [0, 0])),
                       _pair(ctx.attr("dilations", [1, 1])),
                       ctx.attr("groups", 1))
    ctx.set_output("Output", out)


@register_op("depthwise_conv2d")
def _depthwise_conv2d(ctx):
    x = ctx.input("Input")
    w = ctx.input("Filter")
    groups = x.shape[1]
    out = _conv2d_impl(x, w, _pair(ctx.attr("strides", [1, 1])),
                       _pair(ctx.attr("paddings", [0, 0])),
                       _pair(ctx.attr("dilations", [1, 1])), groups)
    ctx.set_output("Output", out)


def _conv_transpose_impl(x, w, s, p, d, nd):
    """Transposed conv as an input-dilated conv with a flipped, IO-swapped
    kernel — the gradient-of-conv identity, so output size is the
    reference's (i-1)*stride - 2*pad + dilation*(k-1) + 1
    (conv_transpose_op.cc). w: [in_c, out_c, *k]."""
    wk = jnp.flip(w, axis=tuple(range(2, 2 + nd))).swapaxes(0, 1)
    pad = [(d[i] * (w.shape[2 + i] - 1) - p[i],) * 2 for i in range(nd)]
    dn = (("NCHW", "OIHW", "NCHW") if nd == 2
          else ("NCDHW", "OIDHW", "NCDHW"))
    x, wk = amp_cast(x, wk)  # bf16 in, bf16 out under AMP (see conv2d)
    return jax.lax.conv_general_dilated(
        x, wk, window_strides=(1,) * nd, padding=pad,
        lhs_dilation=s, rhs_dilation=d,
        dimension_numbers=dn)


@register_op("conv2d_transpose")
def _conv2d_transpose(ctx):
    x = ctx.input("Input")
    w = ctx.input("Filter")  # [in_c, out_c, kh, kw]
    s = _pair(ctx.attr("strides", [1, 1]))
    p = _pair(ctx.attr("paddings", [0, 0]))
    d = _pair(ctx.attr("dilations", [1, 1]))
    ctx.set_output("Output", _conv_transpose_impl(x, w, s, p, d, 2))


@register_op("conv3d")
def _conv3d(ctx):
    x = ctx.input("Input")
    w = ctx.input("Filter")
    s = ctx.attr("strides", [1, 1, 1])
    p = ctx.attr("paddings", [0, 0, 0])
    d = ctx.attr("dilations", [1, 1, 1])
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=tuple(s),
        padding=[(p[0], p[0]), (p[1], p[1]), (p[2], p[2])],
        rhs_dilation=tuple(d),
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=ctx.attr("groups", 1))
    ctx.set_output("Output", out)


# -- pooling ----------------------------------------------------------------

@register_op("pool2d")
def _pool2d(ctx):
    x = ctx.input("X")
    ptype = ctx.attr("pooling_type", "max")
    k = _pair(ctx.attr("ksize", [2, 2]))
    s = _pair(ctx.attr("strides", [2, 2]))
    p = _pair(ctx.attr("paddings", [0, 0]))
    if ctx.attr("global_pooling", False):
        k = (x.shape[2], x.shape[3])
        s = k
        p = (0, 0)
    dims = (1, 1) + k
    strides = (1, 1) + s
    pads = ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1]))
    if ptype == "max":
        init = -jnp.inf
        out = jax.lax.reduce_window(x, init, jax.lax.max, dims, strides, pads)
    else:
        # accumulate avg windows in f32 (bf16 inputs under AMP lose
        # mantissa over 49-element global windows); the converts fuse
        # into the reduce, so the HBM buffers stay input-width
        xf = x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x
        summed = jax.lax.reduce_window(xf, 0.0, jax.lax.add, dims, strides,
                                       pads)
        if ctx.attr("exclusive", True) and (p[0] or p[1]):
            ones = jnp.ones_like(xf)
            counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, dims,
                                           strides, pads)
            out = (summed / counts).astype(x.dtype)
        else:
            out = (summed / (k[0] * k[1])).astype(x.dtype)
    ctx.set_output("Out", out)


@register_op("adaptive_pool2d")
def _adaptive_pool2d(ctx):
    x = ctx.input("X")
    oh, ow = _pair(ctx.attr("pool_size", [1, 1]))
    n, c, h, w = x.shape
    assert h % oh == 0 and w % ow == 0, "adaptive pool needs divisible sizes"
    xr = x.reshape(n, c, oh, h // oh, ow, w // ow)
    if ctx.attr("pooling_type", "avg") == "max":
        out = xr.max(axis=(3, 5))
    else:
        out = xr.mean(axis=(3, 5))
    ctx.set_output("Out", out)


# -- normalization ----------------------------------------------------------

def _bn_bshape(x, ch_axis):
    bshape = [1] * x.ndim
    bshape[ch_axis] = x.shape[ch_axis]
    return tuple(bshape)


def _bn_train(x, scale, bias, red_axes, eps):
    """Train-mode BN forward: single-pass stats (sum / sum-of-squares
    fuse into ONE sweep over x) + a coefficient-form normalize
    (y = x*a + b with per-channel a,b), so that XLA can fuse both into
    the producing conv's fusion. The backward is LEFT TO AUTODIFF on
    purpose (round 3): traced on TPU, XLA also fuses the autodiffed
    backward reductions into the conv gradient fusions, where a
    hand-written custom_vjp backward pinned them as standalone
    convert_reduce fusions (64 of them, ~30ms/step in the device
    trace, against ~0 for this form)."""
    ch_axis = [i for i in range(x.ndim) if i not in red_axes][0]
    bshape = _bn_bshape(x, ch_axis)
    n = 1
    for i in red_axes:
        n *= x.shape[i]
    xf = x.astype(jnp.float32)
    s1 = jnp.sum(xf, axis=red_axes)
    s2 = jnp.sum(xf * xf, axis=red_axes)
    mean = s1 / n
    var = s2 / n - mean * mean          # biased, matching jnp.var
    inv = jax.lax.rsqrt(var + eps)
    a = scale * inv                      # [C] f32
    b = bias - mean * a
    return (xf * a.reshape(bshape) + b.reshape(bshape)).astype(x.dtype)


@register_op("batch_norm")
def _batch_norm(ctx):
    """Inputs: X, Scale, Bias, Mean, Variance. Outputs: Y, MeanOut,
    VarianceOut, SavedMean, SavedVariance (reference: batch_norm_op.cc)."""
    x = ctx.input("X")
    scale = ctx.input("Scale")
    bias = ctx.input("Bias")
    mean_in = ctx.input("Mean")
    var_in = ctx.input("Variance")
    eps = ctx.attr("epsilon", 1e-5)
    momentum = ctx.attr("momentum", 0.9)
    is_test = ctx.attr("is_test", False)

    ch_axis = 1 if ctx.attr("data_layout", "NCHW") == "NCHW" else x.ndim - 1
    red_axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    bshape = _bn_bshape(x, ch_axis)

    if is_test:
        inv = jax.lax.rsqrt(var_in.astype(jnp.float32) + eps)
        a = scale * inv
        b = bias - mean_in * a
        y = (x.astype(jnp.float32) * a.reshape(bshape)
             + b.reshape(bshape)).astype(x.dtype)
        ctx.set_output("Y", y)
        ctx.set_output("MeanOut", mean_in)
        ctx.set_output("VarianceOut", var_in)
        ctx.set_output("SavedMean", mean_in)
        ctx.set_output("SavedVariance", var_in)
        return

    y = _bn_train(x, scale, bias, red_axes, eps)
    # the running-stat updates take their own sums; XLA CSEs them with
    # the forward's
    xf = x.astype(jnp.float32)
    n = 1
    for i in red_axes:
        n *= x.shape[i]
    mean = jnp.sum(xf, axis=red_axes) / n
    var = jnp.sum(xf * xf, axis=red_axes) / n - mean * mean
    ctx.set_output("Y", y)
    ctx.set_output("MeanOut", mean_in * momentum + mean * (1 - momentum))
    ctx.set_output("VarianceOut", var_in * momentum + var * (1 - momentum))
    ctx.set_output("SavedMean", mean)
    ctx.set_output("SavedVariance", jax.lax.rsqrt(var + eps))


@register_op("layer_norm")
def _layer_norm(ctx):
    """Naive mean -> var -> normalize form ON PURPOSE: the round-3
    single-pass/coefficient rewrite (the form that paid off for
    batch_norm) measured 5-12% SLOWER for the transformer in
    order-controlled same-session A/Bs — LN reduces over the minor
    (d_model) dim where XLA fuses the row-local chain fine, and the
    coefficient broadcasts only add traffic."""
    x = ctx.input("X")
    scale = ctx.input("Scale")
    bias = ctx.input("Bias")
    eps = ctx.attr("epsilon", 1e-5)
    begin = ctx.attr("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    y = (xf - mean) / jnp.sqrt(var + eps)
    norm_shape = (1,) * begin + x.shape[begin:]
    if scale is not None:
        y = y * scale.reshape(norm_shape)
    if bias is not None:
        y = y + bias.reshape(norm_shape)
    ctx.set_output("Y", y.astype(x.dtype))
    ctx.set_output("Mean", mean.reshape(x.shape[:begin]))
    ctx.set_output("Variance", var.reshape(x.shape[:begin]))


@register_op("rms_norm")
def _rms_norm(ctx):
    """Root-mean-square norm over the last axis (Zhang & Sennrich,
    arXiv:1910.07467): x / sqrt(mean(x^2) + eps) * Scale, no centring
    and no bias. Statistics in float32 whatever X's width, the result
    at X's width, as layer_norm above."""
    x = ctx.input("X")
    scale = ctx.input("Scale")
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + ctx.attr("epsilon", 1e-6))
    if scale is not None:
        y = y * scale
    ctx.set_output("Y", y.astype(x.dtype))


@register_op("lrn")
def _lrn(ctx):
    x = ctx.input("X")  # NCHW
    n = ctx.attr("n", 5)
    k = ctx.attr("k", 2.0)
    alpha = ctx.attr("alpha", 1e-4)
    beta = ctx.attr("beta", 0.75)
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(n))
    ctx.set_output("Out", x / jnp.power(k + alpha * acc, beta))
    ctx.set_output("MidOut", k + alpha * acc)


# -- dropout ----------------------------------------------------------------

@register_op("dropout")
def _dropout(ctx):
    x = ctx.input("X")
    prob = ctx.attr("dropout_prob", 0.5)
    if ctx.attr("is_test", False) or prob == 0.0:
        ctx.set_output("Out", x)
        ctx.set_output("Mask", jnp.ones_like(x))
        return
    keep = 1.0 - prob
    mask = jax.random.bernoulli(_op_key(ctx), keep, x.shape)
    impl = ctx.attr("dropout_implementation", "downgrade_in_infer")
    if impl == "upscale_in_train":
        out = jnp.where(mask, x / keep, 0.0)
    else:  # reference default: scale at inference instead
        out = jnp.where(mask, x, 0.0)
    ctx.set_output("Out", out.astype(x.dtype))
    ctx.set_output("Mask", mask.astype(x.dtype))


# -- losses -----------------------------------------------------------------

@register_op("cross_entropy", no_grad_slots=["Label"])
def _cross_entropy(ctx):
    x = ctx.input("X")  # probabilities [N, C] (post-softmax)
    x = x.astype(jnp.float32)  # log() of bf16 probs is too coarse
    label = ctx.input("Label")
    eps = 1e-8
    if ctx.attr("soft_label", False):
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        lab = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 \
            else label
        picked = jnp.take_along_axis(
            x, lab[..., None].astype(jnp.int32), axis=-1)
        loss = -jnp.log(picked + eps)
    ctx.set_output("Y", loss)


@register_op("softmax_with_cross_entropy", no_grad_slots=["Label"])
def _softmax_with_cross_entropy(ctx):
    logits = ctx.input("Logits")
    label = ctx.input("Label")
    if ctx.attr("soft_label", False):
        logitsf = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logitsf, axis=-1)
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
        ctx.set_output("Softmax", jnp.exp(logp))
        ctx.set_output("Loss", loss)
        return
    lab = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 \
        else label
    lab = lab.astype(jnp.int32)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    loss = -jnp.take_along_axis(logp, lab[..., None], axis=-1)
    # Softmax output computed independently; dead-code-eliminated by
    # XLA unless a consumer actually reads it
    ctx.set_output("Softmax",
                   jax.nn.softmax(logits.astype(jnp.float32), axis=-1))
    ctx.set_output("Loss", loss)


@register_op("sigmoid_cross_entropy_with_logits", no_grad_slots=["Label"])
def _sigmoid_xent(ctx):
    x = ctx.input("X")
    label = ctx.input("Label")
    loss = jnp.maximum(x, 0.0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ctx.set_output("Out", loss)


@register_op("square_error_cost")
def _square_error_cost(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    ctx.set_output("Out", jnp.square(x - y))


@register_op("smooth_l1_loss")
def _smooth_l1(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    sigma = ctx.attr("sigma", 1.0)
    s2 = sigma * sigma
    diff = x - y
    ad = jnp.abs(diff)
    elem = jnp.where(ad < 1.0 / s2, 0.5 * s2 * diff * diff, ad - 0.5 / s2)
    ctx.set_output("Diff", diff)
    ctx.set_output("Out", jnp.sum(elem, axis=tuple(range(1, x.ndim)),
                                  keepdims=False).reshape(x.shape[0], 1))


@register_op("huber_loss")
def _huber_loss(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    delta = ctx.attr("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= delta, 0.5 * r * r,
                     delta * (ar - 0.5 * delta))
    ctx.set_output("Residual", r)
    ctx.set_output("Out", loss)


@register_op("hinge_loss", no_grad_slots=["Labels"])
def _hinge_loss(ctx):
    logits = ctx.input("Logits")
    labels = ctx.input("Labels")
    ctx.set_output("Loss",
                   jnp.maximum(1.0 - (2.0 * labels - 1.0) * logits, 0.0))


@register_op("log_loss", no_grad_slots=["Labels"])
def _log_loss(ctx):
    pred = ctx.input("Predicted")
    label = ctx.input("Labels")
    eps = ctx.attr("epsilon", 1e-4)
    loss = -label * jnp.log(pred + eps) \
        - (1.0 - label) * jnp.log(1.0 - pred + eps)
    ctx.set_output("Loss", loss)


@register_op("margin_rank_loss", no_grad_slots=["Label"])
def _margin_rank_loss(ctx):
    x1, x2 = ctx.input("X1"), ctx.input("X2")
    label = ctx.input("Label")
    margin = ctx.attr("margin", 0.0)
    out = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    ctx.set_output("Out", out)
    ctx.set_output("Activated", (out > 0).astype(x1.dtype))


@register_op("kldiv_loss", no_grad_slots=["Target"])
def _kldiv_loss(ctx):
    x = ctx.input("X")  # log-probabilities
    target = ctx.input("Target")
    loss = target * (jnp.log(jnp.maximum(target, 1e-12)) - x)
    red = ctx.attr("reduction", "mean")
    if red == "mean":
        loss = jnp.mean(loss)
    elif red == "sum":
        loss = jnp.sum(loss)
    elif red == "batchmean":
        loss = jnp.sum(loss) / x.shape[0]
    ctx.set_output("Loss", loss)


# -- embeddings -------------------------------------------------------------

@register_op("lookup_table", no_grad_slots=["Ids"])
def _lookup_table(ctx):
    """Embedding lookup (reference: lookup_table_op.cc). Ids may carry a
    trailing [.., 1] dim like the reference's LoDTensor ids. With
    is_distributed under an active mesh, the table is row-sharded and
    gathered via shard_map + psum (parallel/sparse.py) — the ICI
    replacement for the reference's pserver prefetch path."""
    w = ctx.input("W")
    ids = ctx.input("Ids")
    if ids.shape and ids.shape[-1] == 1:
        ids = ids.reshape(ids.shape[:-1])
    padding_idx = ctx.attr("padding_idx", -1)
    ids32 = ids.astype(jnp.int32)
    if ctx.attr("is_distributed", False) and \
            ctx.extra.get("mesh") is not None:
        from ..parallel.sparse import sharded_lookup
        out = sharded_lookup(w, ids32,
                             axis=ctx.attr("shard_axis", "model"),
                             mesh=ctx.extra["mesh"],
                             batch_axis=ctx.extra.get("feed_axis"))
    else:
        # explicit clip: jnp.take's default OOB mode is NaN-fill, and
        # the sharded path clips — keep the two paths identical
        out = jnp.take(w, ids32, axis=0, mode="clip")
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    ctx.set_output("Out", out)


@register_op("one_hot", no_grad_slots=["X"])
def _one_hot(ctx):
    x = ctx.input("X")
    depth = ctx.attr("depth")
    if x.shape and x.shape[-1] == 1:
        x = x.reshape(x.shape[:-1])
    ctx.set_output("Out", jax.nn.one_hot(x.astype(jnp.int32), depth,
                                         dtype=jnp.float32))


@register_op("embedding_bag", no_grad_slots=["Ids"])
def _embedding_bag(ctx):
    w = ctx.input("W")
    ids = ctx.input("Ids")  # [batch, bag]
    emb = jnp.take(w, ids.astype(jnp.int32), axis=0)
    mode = ctx.attr("mode", "sum")
    out = emb.sum(axis=1) if mode == "sum" else emb.mean(axis=1)
    ctx.set_output("Out", out)


# -- attention / transformer helpers ---------------------------------------

@register_op("stack")
def _stack(ctx):
    xs = ctx.inputs("X")
    ctx.set_output("Y", jnp.stack(xs, axis=ctx.attr("axis", 0)))


@register_op("unstack")
def _unstack(ctx):
    x = ctx.input("X")
    axis = ctx.attr("axis", 0)
    num = x.shape[axis]
    parts = jnp.split(x, num, axis=axis)
    ctx.set_outputs("Y", [p.squeeze(axis) for p in parts])


@register_op("rotary_embedding", no_grad_slots=["Positions"])
def _rotary_embedding(ctx):
    """Rotary position embedding (Su et al., arXiv:2104.09864) on
    X [..., S, w] with Positions [S]: pair i of a row at position p is
    turned by the angle p * theta^(-2i/r). The pairs are the neighbours
    (x[2i], x[2i+1]) — the complex-number form — or, attr ``layout``
    "half", the columns (x[i], x[i + r/2]) (rotate-half). Attr
    ``rotary_dim`` r < w turns the first r columns and leaves the rest;
    attr ``inv_freq`` [r/2] gives the frequencies as data where theta
    alone does not (YaRN), and ``scale`` multiplies cos and sin (its
    attention factor). Angles and the rotation in float32, the result
    at X's width."""
    x = ctx.input("X")
    pos = ctx.input("Positions").reshape(-1).astype(jnp.float32)
    r = int(ctx.attr("rotary_dim", 0) or x.shape[-1])
    table = ctx.attr("inv_freq", None)
    if table:
        inv_freq = jnp.asarray(table, jnp.float32)
    else:
        inv_freq = 1.0 / (float(ctx.attr("theta", 10000.0)) ** (
            jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angle = pos[:, None] * inv_freq[None, :]              # [S, r/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    scale = float(ctx.attr("scale", 1.0))
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    turned = x[..., :r].astype(jnp.float32)
    if ctx.attr("layout", "interleaved") == "half":
        a, b = turned[..., :r // 2], turned[..., r // 2:]
        out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                              axis=-1)
    else:
        pairs = turned.reshape(x.shape[:-1] + (r // 2, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([a * cos - b * sin, a * sin + b * cos],
                        axis=-1).reshape(turned.shape)
    out = out.astype(x.dtype)
    if r < x.shape[-1]:
        out = jnp.concatenate([out, x[..., r:]], axis=-1)
    ctx.set_output("Out", out)


def _per_shard_attention(attend, mesh, q, k, v, mask, batch_axis,
                         head_axis, head_dim=1):
    """``attend(q, k, v, mask)`` run per shard of a mesh. GSPMD cannot
    partition a Mosaic kernel (the TPU lowering raises "Mosaic kernels
    cannot be automatically partitioned"), so under a ParallelExecutor
    mesh the flash kernel runs inside shard_map over the batch and head
    dims — attention is independent across both, so no collective is
    added. A dim its axis does not divide stays replicated. ``head_dim``
    is where q, k and v hold their heads: 1 head-major, 2
    sequence-major (the mask keeps its heads at dim 1 either way)."""
    from jax.sharding import PartitionSpec as P

    def axis_for(name, dim):
        ok = name in mesh.axis_names and dim % mesh.shape[name] == 0
        return name if ok else None

    b_ax = axis_for(batch_axis, q.shape[0])
    h_ax = axis_for(head_axis, k.shape[head_dim])  # grouped: the key heads
    spec = P(b_ax, h_ax, None, None) if head_dim == 1 else \
        P(b_ax, None, h_ax, None)
    args, specs = (q, k, v), (spec,) * 3
    if mask is not None:
        if mask.ndim == 2:        # [Sq|1, Sk|1], as flash_attention reads it
            mask = mask[None, None]
        elif mask.ndim == 3:      # [B|1, Sq|1, Sk|1]
            mask = mask[:, None]
        args += (mask,)
        specs += (P(b_ax if mask.shape[0] == q.shape[0] else None,
                    h_ax if mask.shape[1] == q.shape[head_dim] else None,
                    None, None),)
    return jax.shard_map(
        lambda q, k, v, mask=None: attend(q, k, v, mask), mesh=mesh,
        in_specs=specs, out_specs=spec, check_vma=False)(*args)


def _count_sdpa_site(ctx, path, mask, causal, window=None, group=1,
                     layout="bhsd"):
    """One count an attention site traced into a step program, in the
    idiom of ops/cache_ops.py _count_append_site (the build-time shape
    inference carries no program and is no site). A grad site that
    replays its forward rule traces it, and counts, again."""
    if "program" not in ctx.extra:
        return
    from ..observability.registry import default_registry
    default_registry().counter(
        "paddle_tpu_sdpa_sites_total",
        "scaled_dot_product_attention sites traced into a step program, "
        "by the path taken (flash: the Pallas kernels; composed: "
        "matmul-softmax-matmul left to XLA; sequence_parallel: ring or "
        "ulysses over a mesh axis; decode_kernel: the Pallas read of a "
        "KV cache bounded by each slot's length), by the mask handed in "
        "(none; "
        "key_row: one value a key, [b,1,1,Sk]; dense: a query axis "
        "longer than 1, which the kernels read a score-sized block of "
        "per tile; kv_len: no mask but each row's live length over a "
        "KV cache, which path decode_kernel reads only the live blocks "
        "of and path composed slices to the bound and masks), by the "
        "causal attr (1 lets the kernels skip the tiles above the "
        "diagonal), by the window attr (0: none; W: query i sees keys "
        "i - W < j <= i and the kernels walk that band alone), by "
        "the query heads that read one key head and by the layout attr "
        "(bhsd: Q, K, V and Out head-major [b,h,S,d]; bshd: sequence-"
        "major [b,S,h,d], as the projections write and read them — "
        "path flash then reads and writes them in place, path composed "
        "transposes inside the rule).",
        ("path", "mask", "causal", "window", "group", "layout")).labels(
            path=path, mask=mask, causal=str(int(causal)),
            window=str(window or 0), group=str(group), layout=layout).inc()


def _decode_kernel_lane_axis(ctx, q, cache, bound):
    """Which path a cached-decode attention site takes, decided on what
    the trace can observe, in the idiom of ops/cache_ops.py
    _append_kernel_lane_axis: the lane axis to hand the Pallas kernel
    (ops/pallas/decode_attention.py), or None for the composition over
    a slice. The kernel runs on a TPU backend, outside a mesh, for one
    query row a slot and a cache it can serve as the device holds it;
    there is no knob."""
    if jax.default_backend() != "tpu" or ctx.extra.get("mesh") is not None \
            or q.ndim != 4 or q.shape[2] != 1:
        return None
    from .cache_ops import device_lane_axis
    from .pallas.decode_attention import fits
    lane_axis = device_lane_axis(cache.shape, cache.dtype)
    return lane_axis if fits(cache.shape, cache.dtype, lane_axis,
                             bound) else None


@register_op("scaled_dot_product_attention",
             no_grad_slots=["Mask", "KvLen"])
def _sdpa(ctx):
    """Fused attention (TPU-native addition; the reference composes it from
    matmul/softmax in python/paddle/fluid/nets.py:312).

    Large shapes on TPU route to the Pallas flash-attention kernel
    (ops/pallas/flash_attention.py) — O(S) memory, online softmax; small
    shapes use the naive composition, which XLA fuses fine. Mask is a
    constant (no_grad_slots) on both paths; a *trainable* additive bias
    should call ops.pallas.flash_attention(bias_grad=True) directly.

    Attr ``layout``: "bhsd" (default) — Q [b, h, Sq, d], K [b, hk, Sk,
    d], V [b, hk, Sk, dv], Out [b, h, Sq, dv]; "bshd" — Q [b, Sq, h, d],
    K [b, Sk, hk, d], V [b, Sk, hk, dv], Out [b, Sq, h, dv], what a
    reshape of a projection's [b, S, h*d] gives with no transpose. The
    flash path reads and writes "bshd" arrays where they lie; the
    composition transposes inside this rule to the arrays a "bhsd" site
    is handed. Mask is [b|1, h|1, Sq|1, Sk] in both. KvLen (the caches
    are head-major) and an active seq_axis refuse "bshd".
    """
    q, k, v = ctx.input("Q"), ctx.input("K"), ctx.input("V")
    mask = ctx.input("Mask")
    causal = bool(ctx.attr("causal", False))
    layout = ctx.attr("layout", "bhsd") or "bhsd"
    if layout not in ("bhsd", "bshd"):
        raise ValueError("scaled_dot_product_attention: layout "
                         f"{layout!r} is neither 'bhsd' nor 'bshd'")
    # where Q, K and V hold their heads and their rows
    head_dim, seq_dim = (2, 1) if layout == "bshd" else (1, 2)
    # attr window W (a causal site's): query i sees keys i - W < j <= i.
    # K and V may come at fewer heads than Q (grouped-query attention:
    # query head h reads key head h // group).
    window = int(ctx.attr("window", 0) or 0) or None
    group = q.shape[head_dim] // k.shape[head_dim] if q.ndim == 4 else 1
    if window is not None and not causal:
        raise ValueError("scaled_dot_product_attention: a window belongs "
                         "to a causal site")

    # Cached decode: K and V are whole KV caches [slots, h, max_seq, d]
    # and KvLen [slots] says how many of each slot's rows are live;
    # attr kv_bound (static) is the most any slot holds this step.
    kv_len = ctx.input("KvLen")
    if kv_len is not None:
        if layout != "bhsd":
            raise ValueError("scaled_dot_product_attention: KvLen reads "
                             "KV caches, which are head-major [slots, h, "
                             "max_seq, d]: layout 'bshd' does not apply")
        if mask is not None or causal:
            raise ValueError("scaled_dot_product_attention: KvLen "
                             "stands in for the mask and for causality")
        bound = int(ctx.attr("kv_bound", k.shape[2]))
        lane_axis = _decode_kernel_lane_axis(ctx, q, k, bound)
        if lane_axis is not None:
            from .pallas.decode_attention import decode_attention
            _count_sdpa_site(ctx, "decode_kernel", "kv_len", causal,
                             group=group)
            ctx.set_output("Out", decode_attention(
                q, k, v, kv_len, bound=bound, lane_axis=lane_axis))
            return
        # the rows under the bound, the dead ones masked: the bits of
        # the [slots,1,1,L] additive mask over a slice this replaces
        k, v = k[:, :, :bound], v[:, :, :bound]
        live = jnp.arange(bound)[None, :] < kv_len[:, None]
        mask = jnp.where(live, 0.0, -1e9).astype(
            jnp.float32)[:, None, None, :]

    # Sequence/context parallelism: attr seq_axis names a mesh axis the
    # sequence dim is sharded over (parallel/context_parallel.py).
    seq_axis = ctx.attr("seq_axis", None)
    mesh = ctx.extra.get("mesh") if ctx.extra else None
    if seq_axis and layout != "bhsd":
        raise ValueError("scaled_dot_product_attention: sequence-parallel "
                         "attention (seq_axis) shards head-major arrays: "
                         "layout 'bshd' does not apply")
    if seq_axis and mesh is not None and seq_axis in mesh.axis_names:
        if window is not None or group != 1:
            raise ValueError("sequence-parallel attention has neither a "
                             "window nor grouped key heads")
        from ..parallel.context_parallel import sequence_parallel_attention
        kv_mask = None
        if mask is not None:
            if mask.ndim == 4 and mask.shape[1] == 1 and mask.shape[2] == 1:
                kv_mask = mask[:, 0, 0, :]        # [b, Sk] key-row mask
            elif mask.ndim == 2:
                kv_mask = mask
            else:
                raise ValueError(
                    "sequence-parallel attention supports key-row masks "
                    "([b,1,1,Sk]); express causality via attr 'causal', "
                    f"got mask shape {mask.shape}")
        _count_sdpa_site(ctx, "sequence_parallel",
                         "none" if mask is None else "key_row", causal)
        ctx.set_output("Out", sequence_parallel_attention(
            q, k, v, mesh, axis=seq_axis,
            impl=ctx.attr("seq_impl", "ring"), causal=causal,
            kv_mask=kv_mask,
            batch_axis=ctx.attr("batch_axis", "data"),
            head_axis=ctx.attr("head_axis", "model")))
        return

    use_flash = ctx.attr("use_flash", None)
    if use_flash is None:
        # the op leaves the choice open: PADDLE_TPU_PALLAS_SDPA decides.
        # "force" engages the kernel anywhere, "0" pins the composition,
        # "1" (a TPU only) leaves it to the flash module's crossover
        from .pallas import pallas_dispatch
        from .pallas.flash_attention import FLASH_CROSSOVER_SEQ as min_seq
        enabled, interp = pallas_dispatch("PADDLE_TPU_PALLAS_SDPA", "1")
        forced = interp is None
        use_flash = (enabled and q.ndim == 4
                     and (forced or (q.shape[seq_dim] >= min_seq
                                     and k.shape[seq_dim] >= min_seq)))
    # the build's shape inference (its context carries no program) asks
    # for Out's shape alone: the composition says it without tracing a
    # kernel body (15-40 ms a site, a second a program of 18)
    use_flash = bool(use_flash) and "program" in ctx.extra
    mask_kind = "kv_len" if kv_len is not None else (
        "none" if mask is None else (
            "dense" if mask.ndim >= 2 and mask.shape[-2] > 1
            else "key_row"))
    _count_sdpa_site(ctx, "flash" if use_flash else "composed", mask_kind,
                     causal, window, group, layout)
    if use_flash:
        from .pallas import flash_attention
        attend = functools.partial(flash_attention, causal=causal,
                                   window=window, layout=layout)
        if mesh is None:
            out = attend(q, k, v, mask)
        else:
            out = _per_shard_attention(
                attend, mesh, q, k, v, mask,
                ctx.attr("batch_axis", "data"),
                ctx.attr("head_axis", "model"), head_dim)
        ctx.set_output("Out", out)
        return
    if layout == "bshd":      # the arrays a head-major site is handed
        q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    scale = 1.0 / np.sqrt(q.shape[-1])
    if group != 1 and kv_len is not None:
        ctx.set_output("Out", _grouped_cached_attention(q, k, v, mask,
                                                        group, scale))
        return
    if group != 1:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    if mask is not None:
        scores = scores + mask
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        qpos = jnp.arange(sq)[:, None]
        kpos = jnp.arange(sk)[None, :]
        seen = qpos >= kpos
        if window is not None:
            seen = seen & (qpos - kpos < window)
        scores = jnp.where(seen, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("...qk,...kd->...qd", probs, v)
    ctx.set_output("Out", jnp.swapaxes(out, 1, 2) if layout == "bshd"
                   else out)


def _grouped_cached_attention(q, k, v, mask, group, scale):
    """The composition over KV caches at ``group`` query heads a key
    head: q [b, hk * group, Sq, d], k and v [b, hk, Sk, d], mask
    [b, 1, 1, Sk]. A key head's query heads are rows of ONE product over
    its keys, so nothing as large as K or V is repeated (repeated, a
    decode step of 96 slots at a bucket of 2048 writes and reads 0.8 GB
    a layer beside the 0.2 GB it attends to). Scores and softmax in
    float32, the products' operands at the arrays' own width (the
    wider of Q's and the cache's)."""
    b, h, sq, d = q.shape
    rows = q.reshape(b, h // group, group * sq, d)
    scores = jnp.einsum("bkqd,bksd->bkqs", rows, k,
                        preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(scores + mask, axis=-1)
    out = jnp.einsum("bkqs,bksd->bkqd",
                     probs.astype(jnp.result_type(q.dtype, v.dtype)), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, sq, v.shape[-1]).astype(q.dtype)


# -- misc -------------------------------------------------------------------

@register_op("nce", no_grad_slots=["Label", "SampleWeight"])
def _nce(ctx):
    """Noise-contrastive estimation loss (reference: nce_op.cc), with
    deterministic uniform sampling of negatives."""
    x = ctx.input("Input")            # [N, D]
    label = ctx.input("Label")        # [N, 1] int
    w = ctx.input("Weight")           # [V, D]
    b = ctx.input("Bias")             # [V]
    num_neg = ctx.attr("num_neg_samples", 10)
    num_total = w.shape[0]
    key = _op_key(ctx)
    neg = jax.random.randint(key, (x.shape[0], num_neg), 0, num_total)
    lab = label.reshape(-1).astype(jnp.int32)

    def logit(ids):
        ww = jnp.take(w, ids, axis=0)       # [..., D]
        bb = jnp.take(b, ids, axis=0) if b is not None else 0.0
        return jnp.einsum("nd,n...d->n...", x, ww) + bb

    pos_logit = logit(lab[:, None]).reshape(-1)      # [N]
    neg_logit = logit(neg)                           # [N, num_neg]
    pos_loss = jax.nn.softplus(-pos_logit)
    neg_loss = jax.nn.softplus(neg_logit).sum(axis=1)
    ctx.set_output("Cost", (pos_loss + neg_loss).reshape(-1, 1))


@register_op("im2sequence", no_grad_slots=[])
def _im2sequence(ctx):
    x = ctx.input("X")  # NCHW
    kh, kw = _pair(ctx.attr("kernels", [1, 1]))
    sh, sw = _pair(ctx.attr("strides", [1, 1]))
    n, c, h, w = x.shape
    oh = (h - kh) // sh + 1
    ow = (w - kw) // sw + 1
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (sh, sw), padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"))  # [n, c*kh*kw, oh, ow]
    out = patches.transpose(0, 2, 3, 1).reshape(n * oh * ow, c * kh * kw)
    ctx.set_output("Out", out)


# -- remaining pool/conv surface (reference: pool_op.cc 3D variants,
# pool_with_index_op.cc, unpool_op.cc, spp_op.cc, roi_pool_op.cc,
# conv_transpose_op.cc 3D) --------------------------------------------------

def _triple(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * 3


@register_op("pool3d")
def _pool3d(ctx):
    x = ctx.input("X")  # NCDHW
    ptype = ctx.attr("pooling_type", "max")
    k = _triple(ctx.attr("ksize", [2, 2, 2]))
    s = _triple(ctx.attr("strides", [2, 2, 2]))
    p = _triple(ctx.attr("paddings", [0, 0, 0]))
    if ctx.attr("global_pooling", False):
        k = tuple(x.shape[2:5])
        s = k
        p = (0, 0, 0)
    dims = (1, 1) + k
    strides = (1, 1) + s
    pads = ((0, 0), (0, 0)) + tuple((pi, pi) for pi in p)
    if ptype == "max":
        out = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, dims, strides,
                                    pads)
    else:
        summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strides,
                                       pads)
        if ctx.attr("exclusive", True) and any(p):
            counts = jax.lax.reduce_window(jnp.ones_like(x), 0.0,
                                           jax.lax.add, dims, strides, pads)
            out = summed / counts
        else:
            out = summed / (k[0] * k[1] * k[2])
    ctx.set_output("Out", out)


def _pool_with_index(x, k, s, p):
    """Max pool + flat argmax index per window via conv patches
    (TPU-friendly: one gather-free argmax over the window axis)."""
    n, c, h, w = x.shape
    patches = jax.lax.conv_general_dilated_patches(
        x, filter_shape=k, window_strides=s,
        padding=[(p[0], p[0]), (p[1], p[1])],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    oh, ow = patches.shape[2], patches.shape[3]
    patches = patches.reshape(n, c, k[0] * k[1], oh, ow)
    # positions of each window element in the (padded) input
    ky, kx = jnp.meshgrid(jnp.arange(k[0]), jnp.arange(k[1]), indexing="ij")
    ky, kx = ky.reshape(-1), kx.reshape(-1)               # [K]
    oy = jnp.arange(oh) * s[0] - p[0]                     # [oh]
    ox = jnp.arange(ow) * s[1] - p[1]                     # [ow]
    rows = oy[None, :] + ky[:, None]                      # [K, oh]
    cols = ox[None, :] + kx[:, None]                      # [K, ow]
    valid = ((rows >= 0) & (rows < h))[:, :, None] & \
            ((cols >= 0) & (cols < w))[:, None, :]        # [K, oh, ow]
    patches = jnp.where(valid[None, None], patches, -jnp.inf)
    widx = jnp.argmax(patches, axis=2)                    # [n, c, oh, ow]
    out = jnp.max(patches, axis=2)
    flat = rows[:, :, None] * w + cols[:, None, :]        # [K, oh, ow]
    index = jnp.take_along_axis(
        jnp.broadcast_to(flat[None, None], (n, c) + flat.shape),
        widx[:, :, None], axis=2).squeeze(2)
    return out, index.astype(jnp.int32)


@register_op("max_pool2d_with_index")
def _max_pool2d_with_index(ctx):
    x = ctx.input("X")
    k = _pair(ctx.attr("ksize", [2, 2]))
    s = _pair(ctx.attr("strides", [2, 2]))
    p = _pair(ctx.attr("paddings", [0, 0]))
    if ctx.attr("global_pooling", False):
        k, s, p = (x.shape[2], x.shape[3]), (x.shape[2], x.shape[3]), (0, 0)
    out, index = _pool_with_index(x, k, s, p)
    ctx.set_output("Out", out)
    ctx.set_output("Mask", index)


@register_op("max_pool3d_with_index")
def _max_pool3d_with_index(ctx):
    """3-D variant: loop the 2-D patch trick over depth slices of the
    pooling window (D is small: the kernel depth)."""
    x = ctx.input("X")  # NCDHW
    k = _triple(ctx.attr("ksize", [2, 2, 2]))
    s = _triple(ctx.attr("strides", [2, 2, 2]))
    p = _triple(ctx.attr("paddings", [0, 0, 0]))
    if ctx.attr("global_pooling", False):
        k = tuple(x.shape[2:5]); s = k; p = (0, 0, 0)
    n, c, d, h, w = x.shape
    od = (d + 2 * p[0] - k[0]) // s[0] + 1
    best_val, best_idx = None, None
    for kd in range(k[0]):
        zs = jnp.arange(od) * s[0] - p[0] + kd          # depth slice per od
        valid = (zs >= 0) & (zs < d)
        sl = x[:, :, jnp.clip(zs, 0, d - 1)]             # [n, c, od, h, w]
        sl = jnp.where(valid[None, None, :, None, None], sl, -jnp.inf)
        # apply 2-D pooling per depth slice by folding od into batch
        v2f = sl.transpose(0, 2, 1, 3, 4).reshape(n * od, c, h, w)
        out2, idx2 = _pool_with_index(v2f, k[1:], s[1:], p[1:])
        oh, ow = out2.shape[2], out2.shape[3]
        out2 = out2.reshape(n, od, c, oh, ow).transpose(0, 2, 1, 3, 4)
        idx2 = idx2.reshape(n, od, c, oh, ow).transpose(0, 2, 1, 3, 4)
        flat = jnp.clip(zs, 0, d - 1)[None, None, :, None, None] * (h * w) \
            + idx2
        if best_val is None:
            best_val, best_idx = out2, flat
        else:
            take = out2 > best_val
            best_val = jnp.where(take, out2, best_val)
            best_idx = jnp.where(take, flat, best_idx)
    ctx.set_output("Out", best_val)
    ctx.set_output("Mask", best_idx.astype(jnp.int32))


@register_op("unpool", no_grad_slots=["Indices"])
def _unpool(ctx):
    """Max-unpool with indices from max_pool2d_with_index (reference:
    unpool_op.cc): scatter pooled values back to their argmax positions."""
    x = ctx.input("X")            # [n, c, oh, ow]
    indices = ctx.input("Indices")
    oh_ow = ctx.attr("unpool_size", None)
    if oh_ow is None:
        ksize = _pair(ctx.attr("ksize", [2, 2]))
        strides = _pair(ctx.attr("strides", ksize))
        h = (x.shape[2] - 1) * strides[0] + ksize[0]
        w = (x.shape[3] - 1) * strides[1] + ksize[1]
    else:
        h, w = _pair(oh_ow)
    n, c = x.shape[0], x.shape[1]
    flat = jnp.zeros((n, c, h * w), x.dtype)
    idx = indices.reshape(n, c, -1).astype(jnp.int32)
    vals = x.reshape(n, c, -1)
    flat = flat.at[jnp.arange(n)[:, None, None],
                   jnp.arange(c)[None, :, None], idx].set(vals)
    ctx.set_output("Out", flat.reshape(n, c, h, w))


@register_op("spp")
def _spp(ctx):
    """Spatial pyramid pooling (reference: spp_op.cc): concat flattened
    adaptive pools at 1x1, 2x2, ... 2^(L-1) bins."""
    x = ctx.input("X")
    levels = ctx.attr("pyramid_height", 3)
    ptype = ctx.attr("pooling_type", "max")
    n, c, h, w = x.shape
    outs = []
    for lv in range(levels):
        bins = 2 ** lv
        kh, kw = -(-h // bins), -(-w // bins)  # ceil
        sh, sw = kh, kw
        ph, pw = (kh * bins - h + 1) // 2, (kw * bins - w + 1) // 2
        dims, strides = (1, 1, kh, kw), (1, 1, sh, sw)
        pads = ((0, 0), (0, 0), (ph, ph), (pw, pw))
        if ptype == "max":
            o = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, dims,
                                      strides, pads)
        else:
            o = jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strides,
                                      pads) / (kh * kw)
        outs.append(o[:, :, :bins, :bins].reshape(n, -1))
    ctx.set_output("Out", jnp.concatenate(outs, axis=1))


@register_op("roi_pool", no_grad_slots=["ROIs"])
def _roi_pool(ctx):
    """Max pooling over regions of interest (reference: roi_pool_op.cc).
    ROIs: [R, 5] = (batch_idx, x1, y1, x2, y2) in input scale; static
    output [R, C, PH, PW] via per-bin masked max (TPU: no dynamic shapes)."""
    x = ctx.input("X")            # [n, c, h, w]
    rois = ctx.input("ROIs")      # [R, 5] float
    ph = ctx.attr("pooled_height", 1)
    pw = ctx.attr("pooled_width", 1)
    scale = ctx.attr("spatial_scale", 1.0)
    n, c, h, w = x.shape

    def one_roi(roi):
        b = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1] * scale).astype(jnp.int32)
        y1 = jnp.round(roi[2] * scale).astype(jnp.int32)
        x2 = jnp.round(roi[3] * scale).astype(jnp.int32)
        y2 = jnp.round(roi[4] * scale).astype(jnp.int32)
        rh = jnp.maximum(y2 - y1 + 1, 1)
        rw = jnp.maximum(x2 - x1 + 1, 1)
        img = x[b]                                    # [c, h, w]
        ys = jnp.arange(h)[None, :]                   # [1, h]
        xs = jnp.arange(w)[None, :]                   # [1, w]
        binh = rh / ph
        binw = rw / pw
        hs = jnp.floor(y1 + jnp.arange(ph)[:, None] * binh).astype(jnp.int32)
        he = jnp.ceil(y1 + (jnp.arange(ph)[:, None] + 1) * binh).astype(jnp.int32)
        ws_ = jnp.floor(x1 + jnp.arange(pw)[:, None] * binw).astype(jnp.int32)
        we = jnp.ceil(x1 + (jnp.arange(pw)[:, None] + 1) * binw).astype(jnp.int32)
        mh = (ys >= hs) & (ys < he) & (ys >= 0) & (ys < h)   # [ph, h]
        mw = (xs >= ws_) & (xs < we) & (xs >= 0) & (xs < w)  # [pw, w]
        m = mh[:, None, :, None] & mw[None, :, None, :]      # [ph, pw, h, w]
        masked = jnp.where(m[None], img[:, None, None], -jnp.inf)
        out = masked.max(axis=(-1, -2))                      # [c, ph, pw]
        return jnp.where(jnp.isfinite(out), out, 0.0)

    ctx.set_output("Out", jax.vmap(one_roi)(rois.astype(jnp.float32)))


@register_op("conv3d_transpose")
def _conv3d_transpose(ctx):
    x = ctx.input("Input")
    w = ctx.input("Filter")  # [in_c, out_c, kd, kh, kw]
    s = _triple(ctx.attr("strides", [1, 1, 1]))
    p = _triple(ctx.attr("paddings", [0, 0, 0]))
    d = _triple(ctx.attr("dilations", [1, 1, 1]))
    ctx.set_output("Output", _conv_transpose_impl(x, w, s, p, d, 3))


def _interp_impl(ctx, method: str):
    """NCHW resize (reference capability: legacy gserver bilinear_interp /
    upsample / resize layers; later-fluid bilinear_interp_op). out size
    from out_h/out_w attrs or a scale factor."""
    x = ctx.input("X")
    n, c, h, w = x.shape
    out_h = int(ctx.attr("out_h", 0) or 0)
    out_w = int(ctx.attr("out_w", 0) or 0)
    scale = float(ctx.attr("scale", 0.0) or 0.0)
    if out_h <= 0 or out_w <= 0:
        if scale <= 0:
            raise ValueError(
                f"{ctx.op.type} needs positive out_h/out_w attrs or a "
                "positive scale attr")
        out_h, out_w = int(h * scale), int(w * scale)
    out = jax.image.resize(x, (n, c, out_h, out_w), method=method)
    ctx.set_output("Out", out.astype(x.dtype))


@register_op("bilinear_interp")
def _bilinear_interp(ctx):
    _interp_impl(ctx, "bilinear")


@register_op("nearest_interp")
def _nearest_interp(ctx):
    _interp_impl(ctx, "nearest")


@register_op("sampling_id", no_grad_slots=["X"])
def _sampling_id(ctx):
    """Sample one class id per row from a probability matrix (reference:
    legacy sampling_id layer; generation-time stochastic decode)."""
    x = ctx.input("X")  # [batch, n_classes] probabilities
    logits = jnp.log(jnp.maximum(x, 1e-20))
    ids = jax.random.categorical(_op_key(ctx), logits, axis=-1)
    ctx.set_output("Out", ids.astype(jnp.int64))



@register_op("mdlstm")
def _mdlstm(ctx):
    """2-D multi-dimensional LSTM (reference: MDLstmLayer,
    paddle/gserver/layers/MDLstmLayer.cpp — grid recurrence where each
    cell sees the states of its LEFT and TOP neighbours). TPU-native
    realization: lax.scan over rows carrying the whole previous row's
    (h, c); an inner scan over columns carries (h_left, c_left). Gate
    pre-activations from the input projection come in as X [b,H,W,5h]
    (i, f_left, f_top, o, g); recurrent weights Wl/Wt are [h, 5h]."""
    x = ctx.input("X")                       # [b, H, W, 5h]
    wl = ctx.input("WeightLeft")             # [h, 5h]
    wt = ctx.input("WeightTop")              # [h, 5h]
    b_, hgt, wid, five_h = x.shape
    hsz = five_h // 5

    def split_gates(g):
        i, fl, ft, o, c = jnp.split(g, 5, axis=-1)
        return (jax.nn.sigmoid(i), jax.nn.sigmoid(fl),
                jax.nn.sigmoid(ft), jax.nn.sigmoid(o), jnp.tanh(c))

    def row_step(row_carry, x_row):
        h_top, c_top = row_carry                 # [b, W, h] each

        def col_step(col_carry, inp):
            h_left, c_left = col_carry           # [b, h]
            x_cell, h_up, c_up = inp             # [b,5h], [b,h], [b,h]
            gates = x_cell + h_left @ wl + h_up @ wt
            i, fl, ft, o, g = split_gates(gates)
            c = i * g + fl * c_left + ft * c_up
            h = o * jnp.tanh(c)
            return (h, c), (h, c)

        zeros = jnp.zeros((b_, hsz), x.dtype)
        (_, _), (h_row, c_row) = jax.lax.scan(
            col_step, (zeros, zeros),
            (x_row.transpose(1, 0, 2),           # [W, b, 5h]
             h_top.transpose(1, 0, 2), c_top.transpose(1, 0, 2)))
        h_row = h_row.transpose(1, 0, 2)         # [b, W, h]
        c_row = c_row.transpose(1, 0, 2)
        return (h_row, c_row), h_row

    zeros_row = jnp.zeros((b_, wid, hsz), x.dtype)
    (_, _), hs = jax.lax.scan(row_step, (zeros_row, zeros_row),
                              x.transpose(1, 0, 2, 3))  # [H, b, W, 5h]
    ctx.set_output("Out", hs.transpose(1, 0, 2, 3))     # [b, H, W, h]
