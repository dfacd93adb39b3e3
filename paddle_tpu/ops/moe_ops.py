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

No token is dropped at any routing. A token picks ``top_k`` DISTINCT
experts, so at most ``min(top_k, experts_held)`` of its picks are held
here and ``tokens * min(top_k, experts_held)`` buffer rows suffice at
the worst. Assignments are sorted by held expert (absent experts last)
and the rows of held experts are multiplied as grouped products
(``jax.lax.ragged_dot``, which reads the group sizes at run time).

The row work follows the LIVE rows, which the routing decides each
step: dispatch, the grouped products, activation and combine run over
the sorted rows a block at a time (``block_rows``: four times a uniform
routing's rows), in a loop whose trip count is read on the device —
as many blocks as hold the live rows, none where no pick is held, all
of the worst case where every pick is. Inside a block the dispatch is a
gather of the block's rows of X and the combine a scatter-add of its
weighted rows into their tokens, each the other's transpose. The layer
is rematerialised in the backward pass a block at a time: a site saves
its inputs, and nothing of a row buffer outlives its block.

Where the block would be the whole worst case (most experts held: every
assignment is live where all are) there is nothing to follow: one pass
under ``jax.checkpoint``, dispatch and combine as row gathers in BOTH
directions through the sort permutation and its inverse, because a sum
by token would there be a scatter-add as long as the assignments.
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


def block_rows(tokens, top_k, held, total):
    """Rows of the buffer the layer's row work runs over at a time: four
    times what a uniform routing sends the held experts, up to a
    multiple of 512 — one block holds a routing four times as heavy as
    the uniform one — and never more than the worst case
    ``tokens * min(top_k, held)``, which it is wherever most of the
    experts are held (every assignment is live where all are)."""
    rows = tokens * min(top_k, held)
    return min(rows, -(-4 * tokens * top_k * held // (total * 512)) * 512)


def _sorted_by_held_expert(idx, held, expert_offset):
    """(order, sizes, n_live) of a routing idx [t, k]: assignment
    j = token * k + pick sits in buffer row r where order[r] = j (the
    assignments sorted by held expert, absent experts last); sizes
    [held] are the experts' row counts and rows [0, n_live) the held
    experts'."""
    local = idx.reshape(-1) - expert_offset
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :],
                    axis=0).astype(jnp.int32)
    return order, sizes, jnp.sum(sizes)


def _gated_ffn_rows(xs, sizes, w_gate, w_up, w_down):
    """down(silu(gate(xs)) * up(xs)) over buffer rows grouped by expert:
    three grouped products that read the group sizes at run time."""
    def grouped(a, w):
        return jax.lax.ragged_dot(a, w, sizes,
                                  preferred_element_type=jnp.float32)

    gate = grouped(xs, w_gate).astype(xs.dtype)
    up = grouped(xs, w_up).astype(xs.dtype)
    act = (jax.nn.silu(gate.astype(jnp.float32))
           * up.astype(jnp.float32)).astype(xs.dtype)
    return grouped(act, w_down).astype(xs.dtype)


# -- the live rows, a block at a time ----------------------------------------
#
# Buffer row r holds assignment order[r] of token order[r] // k. A block
# is ``block`` consecutive rows; ceil(n_live / block) blocks hold every
# live row, and that many run: a loop with a run-time trip count, one
# compiled body each way. Dispatch gathers a block's rows of x, combine
# adds its weighted rows into their tokens, and each is the other's
# transpose: no pass is as long as the worst case or the assignments.

def _blocks_plan(block, idx, held, expert_offset):
    order, sizes, n_live = _sorted_by_held_expert(idx, held, expert_offset)
    # whole blocks to slice: the padding names assignments past the
    # last, each its own, and lies past every live row
    n = order.shape[0]
    order = jnp.concatenate(
        [order, jnp.arange(n, n + -n % block, dtype=order.dtype)])
    return order, sizes, n_live, -(-n_live // block)


def _block(b, block, weights, order, sizes, n_live):
    """Block b's assignments, their tokens and routing weights, which
    rows are live, and the rows of each expert inside the block."""
    tokens, k = weights.shape
    lo = b * block
    mine = jax.lax.dynamic_slice(order, (lo,), (block,))
    ends = jnp.cumsum(sizes)
    inside = jnp.clip(ends, lo, lo + block) \
        - jnp.clip(ends - sizes, lo, lo + block)
    return (mine, jnp.minimum(mine // k, tokens - 1),
            weights.reshape(-1).at[mine].get(mode="fill", fill_value=0.0),
            lo + jnp.arange(block) < n_live, inside)


def _weighted_ffn_rows(live, sizes, xs, w_row, w_gate, w_up, w_down):
    """A block's rows as the combine adds them: float32, times their
    routing weights, zero where no assignment to a held expert sits.
    The grouped products leave such a row as they found it, so it is
    zeroed BEFORE the weighting: masked after, a NaN there would still
    reach the weight's gradient (0 x NaN)."""
    y = _gated_ffn_rows(xs, sizes, w_gate, w_up, w_down)
    y = jnp.where(live[:, None], y.astype(jnp.float32), 0.0)
    return y * w_row[:, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _by_blocks(block, expert_offset, x, idx, weights, w_gate, w_up, w_down):
    order, sizes, n_live, blocks = _blocks_plan(
        block, idx, w_gate.shape[0], expert_offset)

    def add_block(b, out):
        _, token, w_row, live, inside = _block(
            b, block, weights, order, sizes, n_live)
        return out.at[token].add(_weighted_ffn_rows(
            live, inside, x[token], w_row, w_gate, w_up, w_down))

    return jax.lax.fori_loop(0, blocks, add_block,
                             jnp.zeros(x.shape, jnp.float32))


def _by_blocks_fwd(block, expert_offset, *args):
    return _by_blocks(block, expert_offset, *args), args


def _by_blocks_bwd(block, expert_offset, args, g):
    """Each block run again, then its pullback, the gradients summed in
    float32 over the blocks: the layer is rematerialised a block at a
    time, and nothing of a row buffer outlives its block."""
    x, idx, weights, w_gate, w_up, w_down = args
    order, sizes, n_live, blocks = _blocks_plan(
        block, idx, w_gate.shape[0], expert_offset)

    def add_block(b, sums):
        dx, dflat, dmats = sums
        mine, token, w_row, live, inside = _block(
            b, block, weights, order, sizes, n_live)
        _, pullback = jax.vjp(
            functools.partial(_weighted_ffn_rows, live, inside),
            x[token], w_row, w_gate, w_up, w_down)
        dxs, dw_row, *dm = pullback(g[token])
        dx = dx.at[token].add(
            jnp.where(live[:, None], dxs.astype(jnp.float32), 0.0))
        # ``mine`` is part of a permutation: no two gradients meet, and
        # the padding's fall outside and are dropped
        dflat = dflat.at[mine].add(dw_row, unique_indices=True)
        return dx, dflat, [s + d.astype(jnp.float32)
                           for s, d in zip(dmats, dm)]

    def zeros(a):
        return jnp.zeros(a.shape, jnp.float32)

    dx, dflat, dmats = jax.lax.fori_loop(
        0, blocks, add_block,
        (zeros(x), zeros(weights.reshape(-1)),
         [zeros(w_gate), zeros(w_up), zeros(w_down)]))
    return (dx.astype(x.dtype), None, dflat.reshape(weights.shape),
            *[d.astype(w.dtype) for d, w in zip(dmats, args[3:])])


_by_blocks.defvjp(_by_blocks_fwd, _by_blocks_bwd)


# -- one block as long as the worst case: gathers both ways -------------------
#
# Where the block is the whole buffer (most experts held), a sum by token
# would be a scatter-add as long as the assignments. Assignment j sits
# in buffer row rank[j] (rank is order's inverse), and dispatch and
# combine are row gathers in both directions, through the permutation
# and its inverse.

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


def _whole_buffer(expert_offset, x, idx, weights, w_gate, w_up, w_down):
    tokens, k = idx.shape
    held = w_gate.shape[0]
    rows = tokens * min(k, held)
    order, sizes, n_live = _sorted_by_held_expert(idx, held, expert_offset)
    rank = jnp.argsort(order).astype(jnp.int32)
    xs = _dispatch(x, order[:rows] // k, rank, n_live)       # [rows, d]
    y = _gated_ffn_rows(xs, sizes, w_gate, w_up, w_down)     # [rows, d]
    return _combine(y, weights, order, rank, n_live)


def held_experts_ffn(x, idx, weights, w_gate, w_up, w_down, *,
                     expert_offset, experts_total):
    """The held experts' part of a gated-FFN expert layer. x [t, d];
    idx [t, k] expert ids over the whole layer of ``experts_total``;
    weights [t, k]; w_gate, w_up [held, d, f]; w_down [held, f, d].
    Returns [t, d] float32: sum over a token's picks in [offset,
    offset + held) of weight * down(silu(gate(x)) * up(x)).
    Rematerialised in the backward pass: a site saves its inputs."""
    tokens, k = idx.shape
    held = w_gate.shape[0]
    block = block_rows(tokens, k, held, experts_total)
    if block < tokens * min(k, held):
        return _by_blocks(block, expert_offset, x, idx, weights,
                          w_gate, w_up, w_down)
    return jax.checkpoint(functools.partial(_whole_buffer, expert_offset))(
        x, idx, weights, w_gate, w_up, w_down)


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
        "as grouped products over the worst-case row buffer; row_blocks: "
        "the same a block of rows at a time, as many blocks as hold the "
        "step's live rows) and by the experts held of the layer's "
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
    the rows the grouped products multiply. BufferRows (float32 scalar)
    is the rows every other pass of the layer ran over: the blocks of
    ``block_rows`` that held the live rows."""
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
    tokens = idx.size // k
    rows, block = tokens * min(k, held), block_rows(tokens, k, held, total)
    _count_moe_site(ctx, "row_blocks" if block < rows else "ragged_dot",
                    held, total)
    out = held_experts_ffn(
        xa.reshape(-1, d), idx.reshape(-1, k),
        topw.reshape(-1, k).astype(jnp.float32),
        w_gate.reshape(held, d, f), w_up.reshape(held, d, f),
        w_down.reshape(held, f, d), expert_offset=offset,
        experts_total=total)
    ctx.set_output("Out", out.reshape(x.shape).astype(xa.dtype))
    live = jnp.sum((idx >= offset) & (idx < offset + held))
    ctx.set_output("LiveRows", live.astype(jnp.float32))
    ctx.set_output("BufferRows", jnp.asarray(
        -(-live // block) * block if block < rows else rows, jnp.float32))


@register_op("moe_rows_tally")
def _moe_rows_tally(ctx):
    """TallyOut = Tally [4] with one more step's LiveRows and BufferRows
    in it: the live rows' sum over the steps so far, the steps, the
    last step's live rows, and the buffer rows' sum over the steps. The
    layer's persistable state, read from the scope; no gradient."""
    tally = ctx.input("Tally")
    live, buf = (jax.lax.stop_gradient(ctx.input(slot)).astype(tally.dtype)
                 for slot in ("LiveRows", "BufferRows"))
    ctx.set_output("TallyOut", jnp.stack(
        [tally[0] + live, tally[1] + 1, live, tally[3] + buf]))
