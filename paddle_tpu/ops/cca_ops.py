"""Ops of compressed convolutional attention (CCA, arXiv:2510.04476) and
of an MLP router (the ZAYA1 report, arXiv:2511.17127) for a token
server (models/cca_moe.py).

CCA attends in a latent narrower than the model: a token's queries and
keys are projected to ``heads x d_head`` columns, pass two short causal
convolutions along the sequence — a depthwise one, which IS
ops/ssm_ops.py ``causal_conv1d`` / ``conv_state_update``, and one
GROUPED BY HEAD, a ``[d_head, d_head]`` matrix a head a tap, here —
and are joined with their pre-convolution mean and L2-normalised head
by head (``cca_qk_mix``). What a slot carries besides the keys and
values is each convolution's last input.

``grouped_causal_conv1d`` / ``grouped_conv_state_update`` are the two
sides of the grouped convolution, shaped as the depthwise pair is: a
prompt with its ``Length`` (the window it leaves is its last REAL
input's), and one token a slot against the persistable window, in place.

``mlp_router`` is a router that is not one matrix: a down-projection, a
term carried from the previous layer's router, a two-hidden-layer GELU
MLP, a softmax and a top-1 pick; float32 at HIGHEST precision as
ops/moe_ops.py ``moe_router`` is. A row that is no token (an empty slot,
a prompt's padding) is routed NOWHERE: id -1, which ``moe_experts``
treats as an expert it does not hold.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .ssm_ops import (_count_ssm_site, _like, _state_passthrough_infer,
                      _var)


def _taps(w, heads, width):
    """W [K * heads * width, width] as [K, heads, width (in), width
    (out)]: tap k multiplies the input K - 1 - k rows back."""
    return w.reshape(-1, heads, width, width)


def _grouped(rows, w):
    """sum_k rows[k] [.., heads, width] x w[k] [heads, width, width],
    the operands at the rows' width (bfloat16 on the MXU in a served
    model), the sum in float32. Off the TPU the operands are widened
    first — the same products, and XLA's CPU backend runs no batched
    bfloat16 product into float32."""
    mxu = rows[0].dtype if jax.default_backend() == "tpu" else jnp.float32
    return sum(jnp.einsum("...hi,hio->...ho", r.astype(mxu),
                          w[k].astype(mxu),
                          preferred_element_type=jnp.float32)
               for k, r in enumerate(rows))


def _grouped_conv_infer(block_desc, op):
    x, w = _var(block_desc, op, "X"), _var(block_desc, op, "W")
    if x is None or w is None:
        return {}
    taps = w.shape[0] // x.shape[-1]
    return {op.output("Out")[0]: _like(x),
            op.output("State")[0]: _like(
                x, [x.shape[0], (taps - 1) * x.shape[-1]])}


@register_op("grouped_causal_conv1d", no_grad_slots=["Length"],
             infer_shape=_grouped_conv_infer)
def _grouped_causal_conv1d(ctx):
    """Causal convolution of a prompt, grouped by head. X [n, S, C] with
    C = heads * width (attr ``heads``), W [K * C, width] (``_taps``),
    Bias [C], Length [n] int. Out [n, S, C]: K shifted grouped products
    summed in float32, at X's width; State [n, (K - 1) * C]: the last
    K - 1 REAL inputs, oldest first, zeros where the prompt is
    shorter."""
    x = ctx.input("X")
    n, s, ch = x.shape
    heads = int(ctx.attr("heads"))
    w = _taps(ctx.input("W"), heads, ch // heads)
    taps = w.shape[0]
    _count_ssm_site(ctx, "grouped_causal_conv1d", "composed", 0, heads)
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = _grouped([xp[:, k:k + s].reshape(n, s, heads, -1)
                    for k in range(taps)], w).reshape(n, s, ch) \
        + ctx.input("Bias").astype(jnp.float32)
    ctx.set_output("Out", out.astype(x.dtype))
    length = ctx.input("Length").reshape(n).astype(jnp.int32)
    window = jax.vmap(lambda rows, at: jax.lax.dynamic_slice(
        rows, (at, 0), (taps - 1, ch)))(xp, length)
    ctx.set_output("State", window.reshape(n, (taps - 1) * ch))


@register_op("grouped_conv_state_update",
             infer_shape=_state_passthrough_infer)
def _grouped_conv_state_update(ctx):
    """One token a slot through the grouped convolution, its window
    updated in place. State [slots, (K - 1) * C] (oldest input first),
    X [slots, 1, C], W and Bias as above. Out [slots, 1, C]; StateOut is
    State's name: the window moved on by one input."""
    state, x = ctx.input("State"), ctx.input("X")
    slots, _, ch = x.shape
    heads = int(ctx.attr("heads"))
    w = _taps(ctx.input("W"), heads, ch // heads)
    taps = w.shape[0]
    _count_ssm_site(ctx, "grouped_conv_state_update", "composed", 0, heads)
    rows = [state[:, k * ch:(k + 1) * ch].astype(x.dtype)
            for k in range(taps - 1)] + [x.reshape(slots, ch)]
    out = _grouped([r.reshape(slots, heads, -1) for r in rows],
                   w).reshape(slots, ch) \
        + ctx.input("Bias").astype(jnp.float32)
    ctx.set_output("Out", out.reshape(x.shape).astype(x.dtype))
    ctx.set_output("StateOut", jnp.concatenate(
        [r.astype(state.dtype) for r in rows[1:]], axis=1))


@register_op("cca_qk_mix")
def _cca_qk_mix(ctx):
    """The latent queries and keys attention reads, before the rotation.
    Z [.., (heads + kv_heads) * width] is the pre-convolution latent
    ``[q~ | k~]``, B the convolutions' output of it, Tau [kv_heads]
    float32. With c(h) = h // (heads / kv_heads) the key head of query
    head h: ``q[h] = B^q[h] + (q~[h] + k~[c(h)]) / 2``, ``k[c] = B^k[c] +
    (mean over c's query heads of q~[h] + k~[c]) / 2``; then every head
    L2-normalised to length sqrt(width), a key head times its Tau.
    Float32 inside, Q [.., heads * width] and K [.., kv_heads * width]
    at Z's width."""
    z, b = ctx.input("Z"), ctx.input("B")
    heads, kv = int(ctx.attr("heads")), int(ctx.attr("kv_heads"))
    width = z.shape[-1] // (heads + kv)
    lead = z.shape[:-1]

    def split(t):
        t = t.astype(jnp.float32)
        return (t[..., :heads * width].reshape(lead + (kv, heads // kv,
                                                       width)),
                t[..., heads * width:].reshape(lead + (kv, 1, width)))

    (zq, zk), (bq, bk) = split(z), split(b)
    q = bq + (zq + zk) / 2
    k = bk + (jnp.mean(zq, axis=-2, keepdims=True) + zk) / 2

    def unit(t):
        return t * (width ** 0.5) * jax.lax.rsqrt(
            jnp.sum(t * t, axis=-1, keepdims=True)
            + float(ctx.attr("epsilon", 1e-12)))

    k = unit(k) * ctx.input("Tau").astype(jnp.float32)[:, None, None]
    ctx.set_output("Q", unit(q).reshape(lead + (heads * width,))
                   .astype(z.dtype))
    ctx.set_output("K", k.reshape(lead + (kv * width,)).astype(z.dtype))


@register_op("mlp_router", no_grad_slots=["Length", "SelectBias"])
def _mlp_router(ctx):
    """Top-1 routing of X [n, S, d] through an MLP. ``r = X WDown +
    Gamma RPrev`` (RPrev [n, S, h]: the previous layer's r, absent in
    the first layer; Gamma [1]), ``s = gelu(gelu(r W1 + B1) W2 + B2)
    W3`` (the exact, erf GELU), ``p = softmax(s)``, the pick ``argmax(p
    + SelectBias)`` and its weight the pick's own p, not renormalised.
    Length [n] int: row (i, t) is a token where t < Length[i] — a
    prompt's real rows, a decode step's live slots (S = 1, Length 0 for
    an empty one); any other row gets id -1 and weight 0. Always float32
    at HIGHEST precision: a rounded router picks other experts.
    TopIdx [n, S, 1] int32, TopW [n, S, 1] and R [n, S, h] float32,
    Counts [experts + 1] int32: the rows each expert was sent, then the
    experts that were sent any."""
    hi = jax.lax.Precision.HIGHEST

    def f32(slot):
        return ctx.input(slot).astype(jnp.float32)

    x = f32("X")
    r = jnp.dot(x, f32("WDown"), precision=hi)
    if ctx.input("RPrev") is not None:
        r = r + f32("Gamma").reshape(()) * f32("RPrev")
    h = jax.nn.gelu(jnp.dot(r, f32("W1"), precision=hi) + f32("B1"),
                    approximate=False)
    h = jax.nn.gelu(jnp.dot(h, f32("W2"), precision=hi) + f32("B2"),
                    approximate=False)
    p = jax.nn.softmax(jnp.dot(h, f32("W3"), precision=hi), axis=-1)
    experts = p.shape[-1]
    pick = jnp.argmax(jax.lax.stop_gradient(p) + f32("SelectBias"),
                      axis=-1).astype(jnp.int32)
    n, s = x.shape[:2]
    live = jnp.arange(s)[None, :] < ctx.input("Length").reshape(n, 1)
    weight = jnp.take_along_axis(p, pick[..., None], axis=-1)
    ctx.set_output("TopIdx", jnp.where(live, pick, -1)[..., None])
    ctx.set_output("TopW", jnp.where(live[..., None], weight, 0.0))
    ctx.set_output("R", r)
    sent = jnp.sum((pick[..., None] == jnp.arange(experts))
                   & live[..., None], axis=(0, 1)).astype(jnp.int32)
    ctx.set_output("Counts", jnp.concatenate(
        [sent, jnp.sum(sent > 0, keepdims=True).astype(jnp.int32)]))
