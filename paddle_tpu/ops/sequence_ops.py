"""Variable-length sequence ops over ragged batches, and scan-based RNNs.

The reference implements these over LoDTensors with CPU/CUDA kernels that
reorder ragged batches (sequence2batch, reference:
paddle/fluid/operators/math/sequence2batch.h, lstm_op.cc, gru_op.cc,
sequence_pool_op.cc, sequence_softmax_op.cc, sequence_expand_op.cc,
sequence_conv_op.cc, row_conv_op.cc). The TPU-native design: ragged data is
(padded [n, maxlen, ...], lengths) — see core/lod.py — masked compute over
dense tiles keeps the MXU busy, and recurrences are jax.lax.scan so XLA
compiles one fused loop body instead of per-timestep kernel launches.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.lod import RaggedNested, RaggedPair, RaggedTree
from functools import partial

from ..core.registry import register_op

# Every op in this module consumes/produces RaggedPair values natively.
register_op_SEQ = partial(register_op, ragged_aware=True)


def _as_ragged(x) -> RaggedPair:
    if isinstance(x, RaggedPair):
        return x
    if isinstance(x, RaggedNested):
        raise ValueError(
            "this sequence op works on level-1 ragged input but got a "
            "2-level (nested) ragged value — reduce the token level first "
            "(sequence_pool / sequence_last_step) or flatten it with "
            "nested_sequence_flatten")
    # Dense [n, t, ...] with all lengths = t.
    lengths = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    return RaggedPair(x, lengths)


def _pool_padded(x: RaggedPair, ptype: str):
    """Pool the time axis of a level-1 ragged batch -> dense [n, *feat]."""
    data, lengths = x.data, x.lengths
    mask = x.mask()
    for _ in range(data.ndim - 2):
        mask = mask[..., None]
    maskf = mask.astype(data.dtype)
    if ptype == "SUM":
        out = jnp.sum(data * maskf, axis=1)
    elif ptype == "AVERAGE":
        denom = jnp.maximum(lengths, 1).astype(data.dtype)
        denom = denom.reshape((-1,) + (1,) * (data.ndim - 2))
        out = jnp.sum(data * maskf, axis=1) / denom
    elif ptype == "SQRT":
        denom = jnp.sqrt(jnp.maximum(lengths, 1).astype(data.dtype))
        denom = denom.reshape((-1,) + (1,) * (data.ndim - 2))
        out = jnp.sum(data * maskf, axis=1) / denom
    elif ptype == "MAX":
        neg = jnp.finfo(data.dtype).min
        out = jnp.max(jnp.where(mask, data, neg), axis=1)
    elif ptype == "LAST":
        idx = jnp.maximum(lengths - 1, 0)
        out = jnp.take_along_axis(
            data, idx.reshape((-1, 1) + (1,) * (data.ndim - 2)), axis=1
        ).squeeze(1)
    elif ptype == "FIRST":
        out = data[:, 0]
    else:
        raise ValueError(f"unknown pooltype {ptype}")
    return out


def _pool_nested(x: RaggedNested, ptype: str) -> RaggedPair:
    """Pool the innermost (token) level of a 2-level ragged batch; the
    result keeps the outer level (reference LoD semantics: pooling one
    level of a 2-level LoDTensor yields a 1-level LoDTensor)."""
    flat = x.flatten()
    out_flat = _pool_padded(flat, ptype)
    n, s = x.data.shape[:2]
    out = out_flat.reshape((n, s) + out_flat.shape[1:])
    return RaggedPair(out, x.sub_lengths)


@register_op_SEQ("sequence_pool")
def _sequence_pool(ctx):
    x = ctx.input("X")
    ptype = ctx.attr("pooltype", "AVERAGE").upper()
    if isinstance(x, RaggedNested):
        ctx.set_output("Out", _pool_nested(x, ptype))
        return
    ctx.set_output("Out", _pool_padded(_as_ragged(x), ptype))


@register_op_SEQ("nested_sequence_flatten")
def _nested_sequence_flatten(ctx):
    """Nested ragged -> one level shallower, over a batch of n*max_sub
    roots (padding slots have length 0). 2-level input yields a level-1
    ragged batch the RNN/sequence ops consume directly; a depth-k
    RaggedTree yields depth k-1 (apply repeatedly to peel an
    arbitrary-depth LoD — lod_tensor.h:55-107). The inner level of the
    reference's nested RecurrentGradientMachine loop becomes one masked
    batch."""
    x = ctx.input("X")
    if not isinstance(x, (RaggedNested, RaggedTree)):
        raise ValueError("nested_sequence_flatten needs a nested ragged "
                         "input (feed a LoDTensor with >= 2 LoD levels)")
    ctx.set_output("Out", x.flatten())


@register_op_SEQ("nested_sequence_pack", no_grad_slots=["Ref"])
def _nested_sequence_pack(ctx):
    """Dense per-sub-sequence rows [n*max_sub, *feat] (e.g. the inner
    encoder's last states) -> level-1 ragged [n, max_sub, *feat] with the
    outer lengths of Ref (2-level ragged or deeper RaggedTree). Inverse
    of nested_sequence_flatten after the inner levels are reduced away."""
    x = ctx.input("X")
    ref = ctx.input("Ref")
    if not isinstance(ref, (RaggedNested, RaggedTree)):
        raise ValueError("nested_sequence_pack needs a nested ragged Ref")
    if isinstance(x, (RaggedPair, RaggedNested, RaggedTree)):
        raise ValueError(
            "nested_sequence_pack expects DENSE per-sub-sequence rows "
            "[n*max_sub, *feat]; got a ragged value whose inner levels "
            "are still present — reduce them first (sequence_last_step / "
            "sequence_pool)")
    n, s = ref.data.shape[:2]
    outer = ref.sub_lengths if isinstance(ref, RaggedNested) \
        else ref.lengths[0]
    out = x.reshape((n, s) + x.shape[1:])
    ctx.set_output("Out", RaggedPair(out, outer))


@register_op_SEQ("sequence_softmax")
def _sequence_softmax(ctx):
    x = _as_ragged(ctx.input("X"))
    mask = x.mask()
    logits = jnp.where(mask, x.data.squeeze(-1) if x.data.ndim == 3
                       and x.data.shape[-1] == 1 else x.data, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=1)
    probs = jnp.where(mask, probs, 0.0)
    if x.data.ndim == 3 and x.data.shape[-1] == 1:
        probs = probs[..., None]
    ctx.set_output("Out", RaggedPair(probs, x.lengths))


@register_op_SEQ("sequence_expand", no_grad_slots=["Y"])
def _sequence_expand(ctx):
    """Repeat each row of X per the ragged structure of Y
    (reference: sequence_expand_op.cc, level-0 broadcast form)."""
    x = ctx.input("X")          # dense [n, ...]
    y = _as_ragged(ctx.input("Y"))
    xd = x.data if isinstance(x, RaggedPair) else x
    maxlen = y.data.shape[1]
    out = jnp.repeat(xd[:, None], maxlen, axis=1)
    ctx.set_output("Out", RaggedPair(out, y.lengths))


@register_op_SEQ("sequence_concat")
def _sequence_concat(ctx):
    xs = [_as_ragged(v) for v in ctx.inputs("X")]
    # Concatenate along the time axis, compacting each row's valid prefix.
    total_max = sum(x.data.shape[1] for x in xs)
    n = xs[0].data.shape[0]
    feat = xs[0].data.shape[2:]
    out = jnp.zeros((n, total_max) + feat, xs[0].data.dtype)
    lengths = sum((x.lengths for x in xs[1:]), xs[0].lengths)
    pos = jnp.zeros((n,), jnp.int32)
    t_idx = jnp.arange(total_max, dtype=jnp.int32)
    for x in xs:
        src_t = jnp.arange(x.data.shape[1], dtype=jnp.int32)
        # dest positions for this piece: pos[i] + t for t < len_i
        dest = pos[:, None] + src_t[None, :]
        valid = src_t[None, :] < x.lengths[:, None]
        onehot = (dest[:, :, None] == t_idx[None, None, :]) & valid[:, :, None]
        contrib = jnp.einsum("nst,ns...->nt...", onehot.astype(x.data.dtype),
                             x.data)
        out = out + contrib
        pos = pos + x.lengths
    ctx.set_output("Out", RaggedPair(out, lengths))


@register_op_SEQ("sequence_reshape")
def _sequence_reshape(ctx):
    x = _as_ragged(ctx.input("X"))
    new_dim = ctx.attr("new_dim")
    n, t = x.data.shape[:2]
    d = x.data.shape[2] if x.data.ndim > 2 else 1
    factor = (t * d) // new_dim if new_dim else t
    out = x.data.reshape(n, (t * d) // new_dim, new_dim)
    new_len = (x.lengths * d) // new_dim
    ctx.set_output("Out", RaggedPair(out, new_len))


@register_op_SEQ("sequence_slice", no_grad_slots=["Offset", "Length"])
def _sequence_slice(ctx):
    x = _as_ragged(ctx.input("X"))
    offset = ctx.input("Offset").reshape(-1).astype(jnp.int32)
    length = ctx.input("Length").reshape(-1).astype(jnp.int32)
    maxlen = x.data.shape[1]
    t = jnp.arange(maxlen, dtype=jnp.int32)
    src = offset[:, None] + t[None, :]
    src = jnp.minimum(src, maxlen - 1)
    out = jnp.take_along_axis(
        x.data, src.reshape(src.shape + (1,) * (x.data.ndim - 2)), axis=1)
    mask = (t[None, :] < length[:, None])
    maskx = mask.reshape(mask.shape + (1,) * (x.data.ndim - 2))
    ctx.set_output("Out", RaggedPair(out * maskx.astype(out.dtype), length))


@register_op_SEQ("sequence_erase", no_grad_slots=["X"])
def _sequence_erase(ctx):
    x = _as_ragged(ctx.input("X"))
    tokens = jnp.asarray(ctx.attr("tokens", []), jnp.int32)
    data = x.data
    keep = jnp.ones(data.shape[:2], bool)
    for tok in ctx.attr("tokens", []):
        keep &= (data.squeeze(-1) if data.ndim == 3 else data) != tok
    keep &= x.mask()
    # compact kept tokens to the left (stable)
    order = jnp.argsort(~keep, axis=1, stable=True)
    gathered = jnp.take_along_axis(
        data, order.reshape(order.shape + (1,) * (data.ndim - 2)), axis=1)
    new_len = keep.sum(axis=1).astype(jnp.int32)
    t = jnp.arange(data.shape[1], dtype=jnp.int32)
    mask = (t[None, :] < new_len[:, None])
    mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    ctx.set_output("Out", RaggedPair(gathered * mask.astype(data.dtype),
                                     new_len))


@register_op_SEQ("sequence_conv")
def _sequence_conv(ctx):
    """Context-window projection over each sequence
    (reference: sequence_conv_op.cc / ContextProjection function)."""
    x = _as_ragged(ctx.input("X"))
    w = ctx.input("Filter")  # [ctx_len * d, out_d]
    ctx_len = ctx.attr("contextLength", 3)
    ctx_start = ctx.attr("contextStart", -(ctx_len // 2))
    data = x.data  # [n, t, d]
    n, t, d = data.shape
    cols = []
    for i in range(ctx_len):
        shift = ctx_start + i
        rolled = jnp.roll(data, -shift, axis=1)
        tt = jnp.arange(t)
        valid = (tt + shift >= 0) & (tt + shift < t)
        cols.append(jnp.where(valid[None, :, None], rolled, 0.0))
    ctxmat = jnp.concatenate(cols, axis=-1)  # [n, t, ctx_len*d]
    out = jnp.einsum("ntc,co->nto", ctxmat, w)
    mask = x.mask()[..., None].astype(out.dtype)
    ctx.set_output("Out", RaggedPair(out * mask, x.lengths))


@register_op_SEQ("row_conv")
def _row_conv(ctx):
    x = _as_ragged(ctx.input("X"))
    w = ctx.input("Filter")  # [future_ctx, d]
    data = x.data
    k = w.shape[0]
    outs = jnp.zeros_like(data)
    t = data.shape[1]
    for i in range(k):
        rolled = jnp.roll(data, -i, axis=1)
        tt = jnp.arange(t)
        valid = (tt + i < t)
        outs = outs + jnp.where(valid[None, :, None], rolled, 0.0) * w[i][None,
                                                                         None]
    mask = x.mask()[..., None].astype(data.dtype)
    ctx.set_output("Out", RaggedPair(outs * mask, x.lengths))


# -- recurrent nets ---------------------------------------------------------

# Unrolling amortizes the loop's overhead across the small per-step
# recurrent matmuls: 4 won (~ +30% tokens/s on the LSTM-LM bench, A/B on
# a real TPU), 8 regressed.
_SCAN_UNROLL = 4


def _masked_scan_rnn(step, xs, init_states, lengths):
    """Run `step` over time axis 1 of xs, freezing state past each row's
    length. step(carry, x_t) -> (carry, out_t); carry is a tuple."""
    maxlen = xs.shape[1]
    tpos = jnp.arange(maxlen, dtype=jnp.int32)

    def body(carry, inp):
        t, x_t = inp
        new_carry, out_t = step(carry, x_t)
        is_tuple = isinstance(out_t, tuple)
        outs = out_t if is_tuple else (out_t,)
        alive0 = (t < lengths)

        def mask(o):
            a = alive0.reshape((-1,) + (1,) * (o.ndim - 1))
            return o * a.astype(o.dtype)

        sel = lambda n, o: jnp.where(
            alive0.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)
        carry = tuple(sel(n, o) for n, o in zip(new_carry, carry))
        masked = tuple(mask(o) for o in outs)
        return carry, (masked if is_tuple else masked[0])

    xs_t = jnp.moveaxis(xs, 1, 0)  # [t, n, ...]
    carry, outs = jax.lax.scan(body, init_states, (tpos, xs_t),
                               unroll=_SCAN_UNROLL)
    if isinstance(outs, tuple):
        return carry, tuple(jnp.moveaxis(o, 0, 1) for o in outs)
    return carry, jnp.moveaxis(outs, 0, 1)


_ACT = {"sigmoid": jax.nn.sigmoid, "tanh": jnp.tanh, "relu": jax.nn.relu,
        "identity": lambda x: x}


@register_op_SEQ("lstm")
def _lstm(ctx):
    """Dynamic LSTM over ragged input (reference: lstm_op.cc).

    Input: ragged [n, t, 4h] (already projected by a mul op, as in the
    reference), Weight [h, 4h] recurrent weights, Bias [1, 4h] (+ peephole
    terms unsupported). Gate order i, c, f, o matches the reference
    (operators/math/detail/lstm_kernel.h usage in lstm_op).
    """
    x = _as_ragged(ctx.input("Input"))
    w = ctx.input("Weight")
    b = ctx.input("Bias")
    h_dim = w.shape[0]
    n = x.data.shape[0]
    gate_act = _ACT[ctx.attr("gate_activation", "sigmoid")]
    cell_act = _ACT[ctx.attr("cell_activation", "tanh")]
    cand_act = _ACT[ctx.attr("candidate_activation", "tanh")]
    is_reverse = ctx.attr("is_reverse", False)

    data = x.data
    if is_reverse:
        # reverse each sequence's valid prefix
        t = data.shape[1]
        idx = (x.lengths[:, None] - 1 - jnp.arange(t)[None, :]) % t
        data = jnp.take_along_axis(data, idx[..., None], axis=1)

    h0 = ctx.input("H0")
    c0 = ctx.input("C0")
    h0 = h0 if h0 is not None else jnp.zeros((n, h_dim), data.dtype)
    c0 = c0 if c0 is not None else jnp.zeros((n, h_dim), data.dtype)

    # Peephole weights: reference packs them in Bias as [1, 7h] when
    # use_peepholes (lstm_op.cc: W_ic, W_fc, W_oc after the 4h gate bias).
    use_peepholes = ctx.attr("use_peepholes", False) and b is not None \
        and b.reshape(-1).shape[0] >= 7 * h_dim
    if use_peepholes:
        bflat = b.reshape(-1)
        w_ic = bflat[4 * h_dim:5 * h_dim].reshape(1, -1)
        w_fc = bflat[5 * h_dim:6 * h_dim].reshape(1, -1)
        w_oc = bflat[6 * h_dim:7 * h_dim].reshape(1, -1)

    # Hot path: the Pallas fused kernel keeps (h, c) in VMEM across all
    # timesteps (the reference's hl_cuda_lstm.cu analog) — ~13% faster
    # fwd+bwd than the unrolled scan on chip. Standard gates only;
    # PADDLE_TPU_PALLAS_LSTM=0 disables.
    from .pallas import pallas_dispatch
    enabled, interp = pallas_dispatch("PADDLE_TPU_PALLAS_LSTM", "1")
    eligible = (
        not use_peepholes
        and ctx.attr("gate_activation", "sigmoid") == "sigmoid"
        and ctx.attr("cell_activation", "tanh") == "tanh"
        and ctx.attr("candidate_activation", "tanh") == "tanh")
    if enabled and eligible:
        from .pallas.fused_lstm import fused_lstm
        bias = b.reshape(-1)[:4 * h_dim] if b is not None else \
            jnp.zeros((4 * h_dim,), data.dtype)
        h_tm, c_tm, h_last, c_last = fused_lstm(
            jnp.moveaxis(data, 1, 0), w, bias, h0, c0, x.lengths, interp)
        hidden = jnp.moveaxis(h_tm, 0, 1)
        cells = jnp.moveaxis(c_tm, 0, 1)
    else:
        def step(carry, x_t):
            h_prev, c_prev = carry
            gates = x_t + h_prev @ w
            if b is not None:
                gates = gates + b.reshape(1, -1)[:, :4 * h_dim]
            i, c_hat, f, o = jnp.split(gates, 4, axis=-1)
            if use_peepholes:
                i = i + w_ic * c_prev
                f = f + w_fc * c_prev
            i = gate_act(i)
            f = gate_act(f)
            c = f * c_prev + i * cand_act(c_hat)
            if use_peepholes:
                o = o + w_oc * c
            o = gate_act(o)
            h = o * cell_act(c)
            return (h, c), (h, c)

        (h_last, c_last), (hidden, cells) = _masked_scan_rnn(
            step, data, (h0, c0), x.lengths)
    if is_reverse:
        t = hidden.shape[1]
        idx = (x.lengths[:, None] - 1 - jnp.arange(t)[None, :]) % t
        hidden = jnp.take_along_axis(hidden, idx[..., None], axis=1)
        cells = jnp.take_along_axis(cells, idx[..., None], axis=1)
    ctx.set_output("Hidden", RaggedPair(hidden, x.lengths))
    ctx.set_output("Cell", RaggedPair(cells, x.lengths))
    ctx.set_output("LastH", h_last)
    ctx.set_output("LastC", c_last)


@register_op_SEQ("gru")
def _gru(ctx):
    """Dynamic GRU over ragged input (reference: gru_op.cc).
    Input ragged [n, t, 3h] pre-projected; Weight packs [h, 2h] update/reset
    and [h, h] candidate, as in the reference layout."""
    x = _as_ragged(ctx.input("Input"))
    w = ctx.input("Weight")  # [h, 3h]
    b = ctx.input("Bias")
    h_dim = w.shape[0]
    n = x.data.shape[0]
    gate_act = _ACT[ctx.attr("gate_activation", "sigmoid")]
    cand_act = _ACT[ctx.attr("activation", "tanh")]
    w_ur = w[:, :2 * h_dim]
    w_c = w[:, 2 * h_dim:]

    h0 = ctx.input("H0")
    h0 = h0 if h0 is not None else jnp.zeros((n, h_dim), x.data.dtype)

    data = x.data
    is_reverse = ctx.attr("is_reverse", False)
    if is_reverse:
        # reverse each sequence's valid prefix (as the lstm op does)
        t = data.shape[1]
        ridx = (x.lengths[:, None] - 1 - jnp.arange(t)[None, :]) % t
        data = jnp.take_along_axis(data, ridx[..., None], axis=1)

    # default ON: measured ~1.8x over the scan path on v5e (20-layer
    # stacked GRU, b64 t100 h512, marginal-cost protocol, 2 runs each)
    from .pallas import pallas_dispatch
    enabled, interp = pallas_dispatch("PADDLE_TPU_PALLAS_GRU", "1")
    eligible = (ctx.attr("gate_activation", "sigmoid") == "sigmoid"
                and ctx.attr("activation", "tanh") == "tanh")
    if enabled and eligible:
        from .pallas.fused_gru import fused_gru
        gdata = data if b is None else data + b.reshape(1, 1, -1)
        h_tm, h_last = fused_gru(
            jnp.moveaxis(gdata, 1, 0), w, h0, x.lengths, interp)
        hidden = jnp.moveaxis(h_tm, 0, 1)
    else:
        def step(carry, x_t):
            (h_prev,) = carry
            if b is not None:
                x_t = x_t + b.reshape(1, -1)
            xu, xr, xc = jnp.split(x_t, 3, axis=-1)
            ur = h_prev @ w_ur
            hu, hr = jnp.split(ur, 2, axis=-1)
            u = gate_act(xu + hu)
            r = gate_act(xr + hr)
            c = cand_act(xc + (r * h_prev) @ w_c)
            h = u * h_prev + (1 - u) * c
            return (h,), h

        (h_last,), hidden = _masked_scan_rnn(step, data, (h0,),
                                             x.lengths)
    if is_reverse:
        t = hidden.shape[1]
        ridx = (x.lengths[:, None] - 1 - jnp.arange(t)[None, :]) % t
        hidden = jnp.take_along_axis(hidden, ridx[..., None], axis=1)
    ctx.set_output("Hidden", RaggedPair(hidden, x.lengths))
    ctx.set_output("LastH", h_last)


@register_op_SEQ("sequence_mask", no_grad_slots=["X"])
def _sequence_mask(ctx):
    lengths = ctx.input("X").reshape(-1)
    maxlen = ctx.attr("maxlen", -1)
    if maxlen <= 0:
        raise ValueError("sequence_mask on TPU needs a static maxlen attr")
    pos = jnp.arange(maxlen, dtype=lengths.dtype)
    ctx.set_output("Y", (pos[None, :] < lengths[:, None]).astype(jnp.float32))


@register_op_SEQ("sequence_pad")
def _sequence_pad(ctx):
    x = _as_ragged(ctx.input("X"))
    ctx.set_output("Out", x.data)
    ctx.set_output("Length", x.lengths.astype(jnp.int64))


@register_op_SEQ("sequence_unpad", no_grad_slots=["Length"])
def _sequence_unpad(ctx):
    x = ctx.input("X")
    lengths = ctx.input("Length").reshape(-1).astype(jnp.int32)
    ctx.set_output("Out", RaggedPair(x, lengths))


@register_op_SEQ("sequence_last_step")
def _sequence_last_step(ctx):
    x = ctx.input("X")
    if isinstance(x, RaggedNested):
        ctx.set_output("Out", _pool_nested(x, "LAST"))
        return
    ctx.set_output("Out", _pool_padded(_as_ragged(x), "LAST"))


@register_op_SEQ("sequence_first_step")
def _sequence_first_step(ctx):
    x = ctx.input("X")
    if isinstance(x, RaggedNested):
        ctx.set_output("Out", _pool_nested(x, "FIRST"))
        return
    ctx.set_output("Out", _pool_padded(_as_ragged(x), "FIRST"))


# -- CTC (reference: warpctc_op.cc wraps the warp-ctc CUDA lib;
# ctc_align_op.cc / Python ctc_greedy_decoder) ------------------------------

NEG_INF = -1e30


def _ctc_loss_single_batch(logits, logit_lens, labels, label_lens, blank):
    """CTC negative log-likelihood via the standard alpha recursion in log
    space, vectorized over the batch and scanned over time — one fused XLA
    loop instead of the reference's per-sample CUDA kernels.

    logits: [B, T, C] raw (pre-softmax); labels: int32 [B, L] padded.
    """
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    B, T, C = logp.shape
    L = labels.shape[1]
    U = 2 * L + 1

    # Extended label sequence with interleaved blanks: [B, U]
    ext = jnp.full((B, U), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(labels.astype(jnp.int32))
    # allow the s-2 skip where ext[s] is a real label != ext[s-2]
    skip_ok = jnp.zeros((B, U), bool)
    skip_ok = skip_ok.at[:, 2:].set(
        (ext[:, 2:] != blank) & (ext[:, 2:] != ext[:, :-2]))

    # states beyond 2*label_len are invalid
    spos = jnp.arange(U)[None, :]
    state_valid = spos <= 2 * label_lens[:, None]

    emit0 = jnp.take_along_axis(logp[:, 0], ext, axis=1)  # [B, U]
    alpha0 = jnp.where((spos <= 1) & state_valid, emit0, NEG_INF)

    def step(alpha, t):
        emit = jnp.take_along_axis(logp[:, t], ext, axis=1)
        stay = alpha
        prev1 = jnp.concatenate(
            [jnp.full((B, 1), NEG_INF), alpha[:, :-1]], axis=1)
        prev2 = jnp.concatenate(
            [jnp.full((B, 2), NEG_INF), alpha[:, :-2]], axis=1)
        prev2 = jnp.where(skip_ok, prev2, NEG_INF)
        merged = jnp.logaddexp(jnp.logaddexp(stay, prev1), prev2) + emit
        merged = jnp.where(state_valid, merged, NEG_INF)
        # frozen past each sequence's end: carry alpha unchanged
        alive = (t < logit_lens)[:, None]
        return jnp.where(alive, merged, alpha), None

    alpha, _ = jax.lax.scan(step, alpha0, jnp.arange(1, T))
    end1 = 2 * label_lens          # final blank state
    end2 = jnp.maximum(2 * label_lens - 1, 0)  # final label state
    a1 = jnp.take_along_axis(alpha, end1[:, None], axis=1)[:, 0]
    a2 = jnp.take_along_axis(alpha, end2[:, None], axis=1)[:, 0]
    a2 = jnp.where(label_lens > 0, a2, NEG_INF)
    return -jnp.logaddexp(a1, a2)


@register_op_SEQ("warpctc", no_grad_slots=["Label"])
def _warpctc(ctx):
    """CTC loss over ragged logits/labels (reference: warpctc_op.cc).
    Gradients flow through the scan via autodiff — exact, unlike the
    reference's hand-written backward."""
    logits = _as_ragged(ctx.input("Logits"))
    label = _as_ragged(ctx.input("Label"))
    blank = ctx.attr("blank", 0)
    norm_by_times = ctx.attr("norm_by_times", False)
    lab = label.data
    if lab.ndim == 3 and lab.shape[-1] == 1:
        lab = lab[..., 0]
    nll = _ctc_loss_single_batch(logits.data, logits.lengths, lab,
                                 label.lengths, blank)
    if norm_by_times:
        nll = nll / jnp.maximum(logits.lengths, 1).astype(nll.dtype)
    ctx.set_output("Loss", nll[:, None].astype(logits.data.dtype))


@register_op_SEQ("ctc_greedy_decoder", no_grad_slots=["Input"])
def _ctc_greedy_decoder(ctx):
    """Best-path decode: argmax per frame, merge repeats, drop blanks
    (reference: Python ctc_greedy_decoder + ctc_align_op.cc). Static-shape
    compaction via cumsum positions + scatter."""
    x = _as_ragged(ctx.input("Input"))  # [B, T, C] probs or logits
    blank = ctx.attr("blank", 0)
    best = jnp.argmax(x.data, axis=-1).astype(jnp.int32)   # [B, T]
    B, T = best.shape
    mask = x.mask()
    prev = jnp.concatenate([jnp.full((B, 1), -1, jnp.int32),
                            best[:, :-1]], axis=1)
    keep = (best != blank) & (best != prev) & mask
    pos = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1   # target slot
    out_lens = keep.astype(jnp.int32).sum(axis=1)
    # scatter kept tokens into a [B, T] buffer (padded with zeros)
    buf = jnp.zeros((B, T + 1), jnp.int32)
    scatter_pos = jnp.where(keep, pos, T)                  # T = trash slot
    buf = buf.at[jnp.arange(B)[:, None], scatter_pos].set(best)
    ctx.set_output("Out", RaggedPair(buf[:, :T, None], out_lens))


# -- single-step RNN cells (reference: lstm_unit_op.cc, gru_unit_op.cc,
# lstmp_op.cc) --------------------------------------------------------------

@register_op("lstm_unit")
def _lstm_unit(ctx):
    """One LSTM step on pre-projected gates (reference: lstm_unit_op.cc).
    X: [n, 4d] packed i,f,o,g? — the reference packs i, g(c_hat), f, o as
    in lstm_op; C_prev: [n, d]. forget_bias added to f pre-sigmoid."""
    x = ctx.input("X")
    c_prev = ctx.input("C_prev")
    fb = ctx.attr("forget_bias", 0.0)
    d = c_prev.shape[-1]
    i, g, f, o = (x[:, :d], x[:, d:2 * d], x[:, 2 * d:3 * d], x[:, 3 * d:])
    c = jax.nn.sigmoid(f + fb) * c_prev + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    ctx.set_output("C", c)
    ctx.set_output("H", h)


@register_op("gru_unit")
def _gru_unit(ctx):
    """One GRU step (reference: gru_unit_op.cc). Input: [n, 3d] projected
    x contributions; HiddenPrev [n, d]; Weight [d, 3d]; Bias [1, 3d]."""
    x = ctx.input("Input")
    h_prev = ctx.input("HiddenPrev")
    w = ctx.input("Weight")
    b = ctx.input("Bias")
    d = h_prev.shape[-1]
    if b is not None:
        x = x + b.reshape(1, -1)
    xu, xr, xc = x[:, :d], x[:, d:2 * d], x[:, 2 * d:]
    hu = h_prev @ w[:, :d]
    hr = h_prev @ w[:, d:2 * d]
    u = jax.nn.sigmoid(xu + hu)
    r = jax.nn.sigmoid(xr + hr)
    c = jnp.tanh(xc + (r * h_prev) @ w[:, 2 * d:])
    h = u * h_prev + (1.0 - u) * c
    ctx.set_output("Gate", jnp.concatenate([u, r, c], axis=-1))
    ctx.set_output("ResetHiddenPrev", r * h_prev)
    ctx.set_output("Hidden", h)


@register_op_SEQ("lstmp")
def _lstmp(ctx):
    """LSTM with recurrent projection (reference: lstmp_op.cc): cell size
    d, projected hidden size p; recurrence runs on the projection."""
    x = _as_ragged(ctx.input("Input"))       # [n, t, 4d] pre-projected
    w = ctx.input("Weight")                  # [p, 4d]
    w_proj = ctx.input("ProjWeight")         # [d, p]
    b = ctx.input("Bias")
    d = w_proj.shape[0]
    p = w_proj.shape[1]
    n = x.data.shape[0]
    gate_act = _ACT[ctx.attr("gate_activation", "sigmoid")]
    cell_act = _ACT[ctx.attr("cell_activation", "tanh")]
    cand_act = _ACT[ctx.attr("candidate_activation", "tanh")]
    proj_act = _ACT[ctx.attr("proj_activation", "tanh")]

    h0 = ctx.input("H0")
    c0 = ctx.input("C0")
    r0 = jnp.zeros((n, p), x.data.dtype) if h0 is None else h0 @ w_proj \
        if h0.shape[-1] == d else h0
    c0 = c0 if c0 is not None else jnp.zeros((n, d), x.data.dtype)

    use_peepholes = ctx.attr("use_peepholes", False) and b is not None \
        and b.reshape(-1).shape[0] >= 7 * d
    if use_peepholes:
        bflat = b.reshape(-1)
        w_ic = bflat[4 * d:5 * d].reshape(1, -1)
        w_fc = bflat[5 * d:6 * d].reshape(1, -1)
        w_oc = bflat[6 * d:7 * d].reshape(1, -1)

    def step(carry, x_t):
        r_prev, c_prev = carry
        gates = x_t + r_prev @ w
        if b is not None:
            gates = gates + b.reshape(1, -1)[:, :4 * d]
        i, c_hat, f, o = jnp.split(gates, 4, axis=-1)
        if use_peepholes:
            i = i + w_ic * c_prev
            f = f + w_fc * c_prev
        c = gate_act(f) * c_prev + gate_act(i) * cand_act(c_hat)
        if use_peepholes:
            o = o + w_oc * c
        h = gate_act(o) * cell_act(c)
        r = proj_act(h @ w_proj)
        return (r, c), (r, c)

    (r_last, c_last), (proj, cells) = _masked_scan_rnn(
        step, x.data, (r0, c0), x.lengths)
    ctx.set_output("Projection", RaggedPair(proj, x.lengths))
    ctx.set_output("Cell", RaggedPair(cells, x.lengths))
    ctx.set_output("LastH", r_last)
    ctx.set_output("LastC", c_last)


@register_op_SEQ("ctc_align", no_grad_slots=["Input"])
def _ctc_align(ctx):
    """Merge repeated tokens (optional) then drop blanks (reference:
    ctc_align_op.cc). Static-shape compaction as in ctc_greedy_decoder."""
    x = _as_ragged(ctx.input("Input"))      # [B, T, 1] or [B, T] token ids
    blank = ctx.attr("blank", 0)
    merge = ctx.attr("merge_repeated", True)
    ids = x.data
    if ids.ndim == 3 and ids.shape[-1] == 1:
        ids = ids[..., 0]
    ids = ids.astype(jnp.int32)
    B, T = ids.shape
    mask = x.mask()
    keep = (ids != blank) & mask
    if merge:
        prev = jnp.concatenate([jnp.full((B, 1), -1, jnp.int32),
                                ids[:, :-1]], axis=1)
        keep = keep & (ids != prev)
    pos = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1
    out_lens = keep.astype(jnp.int32).sum(axis=1)
    buf = jnp.zeros((B, T + 1), jnp.int32)
    scatter_pos = jnp.where(keep, pos, T)
    buf = buf.at[jnp.arange(B)[:, None], scatter_pos].set(ids)
    ctx.set_output("Output", RaggedPair(buf[:, :T, None], out_lens))


@register_op_SEQ("sequence_reverse")
def _sequence_reverse(ctx):
    """Reverse each sequence's valid prefix, padding stays in place
    (reference: sequence_reverse_op.h). Powers reverse=True recurrences
    built on the masked-scan DynamicRNN."""
    x = _as_ragged(ctx.input("X"))
    t = jnp.arange(x.data.shape[1], dtype=jnp.int32)
    lens = x.lengths.astype(jnp.int32)
    src = jnp.where(t[None, :] < lens[:, None],
                    lens[:, None] - 1 - t[None, :], t[None, :])
    out = jnp.take_along_axis(
        x.data, src.reshape(src.shape + (1,) * (x.data.ndim - 2)),
        axis=1)
    ctx.set_output("Y", RaggedPair(out, x.lengths))


@register_op_SEQ("multihead_seq_attention")
def _multihead_seq_attention(ctx):
    """Multi-head self/cross attention over RAGGED sequences (the v2
    networks.multi_head_attention composition, reference:
    trainer_config_helpers/networks.py:1580 — realized as one fused
    ragged op so padding is masked exactly; the modern dense-tensor
    path is ops 'scaled_dot_product_attention')."""
    q = _as_ragged(ctx.input("Q"))
    k = _as_ragged(ctx.input("K"))
    v = _as_ragged(ctx.input("V"))
    wq, wk = ctx.input("WQ"), ctx.input("WK")
    wv, wo = ctx.input("WV"), ctx.input("WO")
    heads = ctx.attr("num_heads", 1)
    qp = jnp.einsum("btd,de->bte", q.data, wq)
    kp = jnp.einsum("btd,de->bte", k.data, wk)
    vp = jnp.einsum("btd,de->bte", v.data, wv)
    b, t, d = qp.shape
    dh = d // heads

    def split(x):
        return x.reshape(b, x.shape[1], heads, dh).transpose(0, 2, 1, 3)

    qs, ks, vs = split(qp), split(kp), split(vp)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qs, ks) / jnp.sqrt(
        jnp.asarray(dh, qp.dtype))
    scores = jnp.where(k.mask()[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vs) \
        .transpose(0, 2, 1, 3).reshape(b, t, d)
    out = jnp.einsum("btd,de->bte", out, wo)
    out = out * q.mask()[..., None].astype(out.dtype)
    ctx.set_output("Out", RaggedPair(out, q.lengths))
