"""Sparse mixture-of-experts ops: the router, and the expert layer as one
chip of an expert-parallel group runs it.

The reference has no expert layer (its sparse path is the pserver's
row-sparse embedding update); this is a TPU-native addition in the shape
expert parallelism needs: ``moe_router`` scores every token against ALL
the experts of the layer, and ``moe_experts`` is TOLD which contiguous
range of them it holds (``expert_offset``, ``experts_held`` of
``experts_total``) and computes that range's part of the result for the
tokens routed into it. What the absent experts would add is another
chip's part; on one chip the layer simply runs without the exchange.

No token is dropped at any routing. The row buffers are static and sized
for the worst case a routing can give: a token picks ``top_k`` DISTINCT
experts, so at most ``min(top_k, experts_held)`` of its picks are held
here and ``tokens * min(top_k, experts_held)`` rows always suffice.
Assignments are sorted by held expert (absent experts last), the rows of
held experts are multiplied as grouped products (``jax.lax.ragged_dot``,
which reads the group sizes at run time), and rows past the live count
carry weight zero. Dispatch and combine are row gathers in BOTH
directions (each is the other's transpose through the sort permutation
and its inverse), so no pass holds a scatter-add; the whole layer is
rematerialised in the backward pass, so a site saves its inputs and
nothing of the row buffers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..amp import amp_cast
from ..core.registry import register_op


@register_op("moe_router", no_grad_slots=["Bias"])
def _moe_router(ctx):
    """Scores X [..., d] against W [d, experts_total] and picks attr
    ``top_k`` experts a token. Scores are sigmoids; selection is by
    score + Bias (a selection bias that is no part of the weights: the
    auxiliary-loss-free balancing of arXiv:2408.15664), the weights are
    the picked experts' own scores, normalised to sum 1 and scaled by
    ``routed_scaling_factor``. Always float32 at HIGHEST matmul
    precision, AMP or not: a rounded gate picks other experts.
    TopIdx [..., k] int32, TopW [..., k] float32."""
    x, w, bias = ctx.input("X"), ctx.input("W"), ctx.input("Bias")
    scores = jax.nn.sigmoid(
        jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
    select = scores if bias is None else scores + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(select),
                           int(ctx.attr("top_k")))
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    ctx.set_output("TopIdx", idx.astype(jnp.int32))
    ctx.set_output("TopW",
                   picked * float(ctx.attr("routed_scaling_factor", 1.0)))


# -- dispatch and combine: gathers both ways --------------------------------
#
# Assignment j = token * k + pick sits in buffer row rank[j]; buffer row r
# holds assignment order[r] (order sorts the assignments by held expert,
# rank is its inverse); rows [0, n_live) belong to held experts.

@jax.custom_vjp
def _dispatch(x, token, rank, n_live):
    """x [t, d] -> buffer rows [rows, d]: row r is token[r], the token
    of the assignment that sits there."""
    del rank, n_live
    return x[token]


def _dispatch_fwd(x, token, rank, n_live):
    return x[token], (x.shape[0], rank, n_live)


def _dispatch_bwd(res, g):
    tokens, rank, n_live = res
    return (_collect(g, rank, n_live, tokens).astype(g.dtype),
            None, None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _collect(rows, rank, n_live, tokens, weights=None):
    """The transpose of the dispatch, as a gather: for every token the
    sum over its picks of the buffer row its assignment sits in
    (optionally weighted), picks of absent experts contributing
    nothing. Accumulated in float32."""
    live = rank < n_live                                    # [t * k]
    picked = rows[jnp.minimum(rank, rows.shape[0] - 1)]
    picked = jnp.where(live[:, None], picked.astype(jnp.float32), 0.0)
    if weights is not None:
        picked = picked * weights.reshape(-1, 1)
    return jnp.sum(picked.reshape(tokens, -1, rows.shape[-1]), axis=1)


@jax.custom_vjp
def _combine(y, weights, order, rank, n_live):
    """Buffer rows y [rows, d] -> tokens [t, d] float32: each token's
    weighted sum over its picks held here."""
    del order
    return _collect(y, rank, n_live, weights.shape[0], weights)


def _combine_fwd(y, weights, order, rank, n_live):
    return (_collect(y, rank, n_live, weights.shape[0], weights),
            (y, weights, order, rank, n_live))


def _combine_bwd(res, g):
    y, weights, order, rank, n_live = res
    tokens, k = weights.shape
    row = jnp.arange(y.shape[0])
    mine = order[:y.shape[0]]
    w_row = jnp.where(row < n_live, weights.reshape(-1)[mine], 0.0)
    dy = (g[mine // k] * w_row[:, None]).astype(y.dtype)
    live = (rank < n_live).reshape(tokens, k)
    y_pick = y[jnp.minimum(rank, y.shape[0] - 1)].astype(jnp.float32)
    dw = jnp.einsum("td,tkd->tk", g, y_pick.reshape(tokens, k, -1))
    return dy, jnp.where(live, dw, 0.0), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def held_experts_ffn(x, idx, weights, w_gate, w_up, w_down, *,
                     expert_offset):
    """The held experts' part of a gated-FFN expert layer. x [t, d];
    idx [t, k] expert ids over the whole layer; weights [t, k];
    w_gate, w_up [held, d, f]; w_down [held, f, d]. Returns [t, d]
    float32: sum over a token's picks in [offset, offset + held) of
    weight * down(silu(gate(x)) * up(x))."""
    tokens, k = idx.shape
    held = w_gate.shape[0]
    rows = tokens * min(k, held)
    local = idx.reshape(-1) - expert_offset
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    rank = jnp.argsort(order).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :],
                    axis=0).astype(jnp.int32)
    n_live = jnp.sum(sizes)

    def grouped(a, w):
        return jax.lax.ragged_dot(a, w, sizes,
                                  preferred_element_type=jnp.float32)

    xs = _dispatch(x, order[:rows] // k, rank, n_live)       # [rows, d]
    gate = grouped(xs, w_gate).astype(x.dtype)
    up = grouped(xs, w_up).astype(x.dtype)
    act = (jax.nn.silu(gate.astype(jnp.float32))
           * up.astype(jnp.float32)).astype(x.dtype)
    y = grouped(act, w_down).astype(x.dtype)                 # [rows, d]
    return _combine(y, weights, order, rank, n_live)


def _count_moe_site(ctx, path, held, total):
    """One count an expert-layer site traced into a step program, in
    the idiom of ops/nn_ops.py _count_sdpa_site."""
    if "program" not in ctx.extra:
        return
    from ..observability.registry import default_registry
    default_registry().counter(
        "paddle_tpu_moe_sites_total",
        "moe_experts sites traced into a step program, by the path "
        "taken (ragged_dot: rows sorted by held expert and multiplied "
        "as grouped products) and by the experts held of the layer's "
        "total.", ("path", "held", "total")).labels(
            path=path, held=str(held), total=str(total)).inc()


@register_op("moe_experts", no_grad_slots=["TopIdx"])
def _moe_experts(ctx):
    """Out = the part of a gated-FFN expert layer that experts
    [expert_offset, expert_offset + experts_held) of experts_total give
    for X [..., d] under the routing TopIdx/TopW [..., top_k]. WGate and
    WUp are the held experts' matrices stacked by rows
    [experts_held * d, f], WDown [experts_held * f, d]. Matmul operands
    and the row buffers follow AMP (bf16), accumulation and the combine
    are float32, Out is at X's width. LiveRows (float32 scalar) is what
    the routing sent here: the assignments to held experts, which is
    the rows the grouped products multiply."""
    x = ctx.input("X")
    idx, topw = ctx.input("TopIdx"), ctx.input("TopW")
    held = int(ctx.attr("experts_held"))
    total = int(ctx.attr("experts_total"))
    offset = int(ctx.attr("expert_offset", 0))
    if not 0 <= offset <= total - held:
        raise ValueError(f"moe_experts: experts [{offset}, {offset + held})"
                         f" are not among {total}")
    d, k = x.shape[-1], idx.shape[-1]
    if k != int(ctx.attr("top_k", k)):
        raise ValueError("moe_experts: TopIdx does not hold top_k picks")
    xa, w_gate, w_up, w_down = amp_cast(
        x, ctx.input("WGate"), ctx.input("WUp"), ctx.input("WDown"))
    f = w_gate.shape[-1]
    _count_moe_site(ctx, "ragged_dot", held, total)
    ffn = jax.checkpoint(functools.partial(held_experts_ffn,
                                           expert_offset=offset))
    out = ffn(xa.reshape(-1, d), idx.reshape(-1, k),
              topw.reshape(-1, k).astype(jnp.float32),
              w_gate.reshape(held, d, f), w_up.reshape(held, d, f),
              w_down.reshape(held, f, d))
    ctx.set_output("Out", out.reshape(x.shape).astype(xa.dtype))
    ctx.set_output("LiveRows", jnp.sum(
        (idx >= offset) & (idx < offset + held)).astype(jnp.float32))


@register_op("moe_rows_tally")
def _moe_rows_tally(ctx):
    """TallyOut = Tally [3] with one more step's LiveRows in it: their
    sum over the steps so far, the steps, and the last step's. The
    layer's persistable state, read from the scope; no gradient."""
    tally, live = ctx.input("Tally"), ctx.input("LiveRows")
    live = jax.lax.stop_gradient(live).astype(tally.dtype)
    ctx.set_output("TallyOut",
                   jnp.stack([tally[0] + live, tally[1] + 1, live]))
