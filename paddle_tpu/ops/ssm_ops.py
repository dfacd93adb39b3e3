"""State-space (Mamba-2) and causal-convolution ops for a token server
(Dao & Gu, arXiv:2405.21060; serving/generation, models/hybrid_ssm.py).

A Mamba-2 mixer keeps two kinds of state a sequence: the recurrent
state, one [d_state, heads * d_head] float32 matrix a layer, and the
last ``d_conv - 1`` inputs of its depthwise causal convolution. A head
h at position t holds

    S_t = exp(dt_t A_h) S_(t-1) + dt_t B_t x_t^T        y_t = S_t^T C_t + D_h x_t

with ``dt_t = softplus(raw_t + dt_bias)``, ``A = -exp(A_log)`` one
scalar a head and B_t, C_t [d_state] shared by the heads (one group).

The state is held TRANSPOSED, [.., d_state, heads * d_head]: everything
that varies by head and column (x, dt, the decay, y) then lies along the
device's lanes as a dense row, and only B and C, which all heads share,
lie along the sublanes. Held [.., heads, d_head, d_state] a decode
step's x would have to reach the kernel one value a sublane, an operand
padded 128 times over — as large as the state itself.

``ssd_prefill`` runs a whole prompt by the chunked algorithm: inside a
chunk the masked product ``(C B^T * decay) X``, between chunks a scan
over the chunk states; the products take their operands at X's width
(bfloat16 on the MXU in a served model, float32 in a test), decays,
cumulative sums and the state are float32. ``ssm_state_update`` is one
token a slot against the persistable state, in place (the output takes
the input's name, as ops/cache_ops.py kv_cache_append's does, so the
executor donates it): one Pallas call a layer on a TPU
(ops/pallas/ssm_state_update.py), the jax.numpy composition elsewhere.
``causal_conv1d`` / ``conv_state_update`` are the convolution's two
sides, and ``slot_state_write`` puts a prefill's states into a slot.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op


def _count_ssm_site(ctx, op, path, chunk=0, group=1):
    """One count a state-space site traced into a step program, in the
    idiom of ops/nn_ops.py _count_sdpa_site (the build's shape
    inference carries no program and is no site)."""
    if "program" not in ctx.extra:
        return
    from ..observability.registry import default_registry
    default_registry().counter(
        "paddle_tpu_ssm_sites_total",
        "State-space and causal-convolution sites traced into a step "
        "program, by op (ssd_prefill, ssm_state_update, causal_conv1d, "
        "conv_state_update), by the path taken (chunked: the chunked "
        "scan of a prompt; kernel: the Pallas state update of a decode "
        "step; composed: jax.numpy left to XLA), by the chunk length "
        "(0 where none applies) and by the heads that share one B and "
        "C (all of them under one group).",
        ("op", "path", "chunk", "group")).labels(
            op=op, path=path, chunk=str(chunk), group=str(group)).inc()


def _var(block_desc, op, slot):
    names = op.input(slot)
    v = block_desc.find_var_recursive(names[0]) if names else None
    return v if v is not None and v.shape is not None else None


def _like(v, shape=None, dtype=None):
    return {"shape": list(v.shape if shape is None else shape),
            "dtype": dtype or v.dtype, "lod_level": 0}


def _state_passthrough_infer(block_desc, op):
    """Out mirrors X, StateOut mirrors State (the in-place ops)."""
    x, st = _var(block_desc, op, "X"), _var(block_desc, op, "State")
    if x is None or st is None:
        return {}
    out = {op.output("StateOut")[0]: _like(st)}
    for slot in ("Out", "Y"):
        if op.output(slot):
            out[op.output(slot)[0]] = _like(x)
    return out


def step_inputs(dt_raw, a_log, dt_bias):
    """(dt [.., H], A [H]) in float32: the step softplus(raw + bias),
    unclamped, and A = -exp(A_log)."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + dt_bias.astype(jnp.float32))
    return dt, -jnp.exp(a_log.astype(jnp.float32))


# -- the recurrent state ------------------------------------------------

def ssd_chunked(x, dt, a, b, c, chunk):
    """One batch of sequences by the chunked algorithm. x [n, S, H, P],
    dt [n, S, H] float32 (0 at a row that is no token: it neither
    decays nor feeds the state), a [H] float32, b, c [n, S, N]. Returns
    (y [n, S, H, P] float32 without the D term, state [n, N, H * P]
    float32 after the last row). S is padded to a whole number of
    chunks with rows of dt 0."""
    n, s, h, p = x.shape
    mxu = x.dtype
    q = min(int(chunk), s)
    pad = -s % q
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),)
                               * (t.ndim - 2)) for t in (x, dt, b, c))
    nc = (s + pad) // q
    x = x.reshape(n, nc, q, h, p)
    dt = dt.reshape(n, nc, q, h)
    b = b.reshape(n, nc, q, -1).astype(mxu)
    c = c.reshape(n, nc, q, -1).astype(mxu)
    cum = jnp.cumsum(dt * a, axis=2)                       # [n,nc,q,H]
    # inside a chunk: row i reads row j <= i through exp(cum_i - cum_j)
    cum_h = jnp.moveaxis(cum, 3, 2)                        # [n,nc,H,q]
    seen = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(
        seen, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf))
    scores = jnp.einsum("zcin,zcjn->zcij", c, b,
                        preferred_element_type=jnp.float32)
    weights = scores[:, :, None] * decay \
        * jnp.moveaxis(dt, 3, 2)[..., None, :]             # [n,nc,H,q,q]
    y = jnp.einsum("zchij,zcjhp->zcihp", weights.astype(mxu), x,
                   preferred_element_type=jnp.float32)
    # what each chunk leaves its end: rows weighted by the decay from
    # the row to the chunk's last
    to_end = jnp.exp(cum[:, :, -1:, :] - cum) * dt         # [n,nc,q,H]
    left = jnp.einsum("zcjn,zcjhp->zcnhp", b,
                      (x.astype(jnp.float32)
                       * to_end[..., None]).astype(mxu),
                      preferred_element_type=jnp.float32)
    whole = jnp.exp(cum[:, :, -1, :])                      # [n,nc,H]

    def carry(state, inp):
        keep, add = inp
        return state * keep[:, None, :, None] + add, state

    final, before = jax.lax.scan(
        carry, jnp.zeros_like(left[:, 0]),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(left, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                    # [n,nc,N,H,P]
    y = y + jnp.einsum("zcin,zcnhp->zcihp", c, before.astype(mxu),
                       preferred_element_type=jnp.float32) \
        * jnp.exp(cum)[..., None]
    return (y.reshape(n, nc * q, h, p)[:, :s],
            final.reshape(n, final.shape[1], h * p))


def _ssd_prefill_infer(block_desc, op):
    x, b = _var(block_desc, op, "X"), _var(block_desc, op, "B")
    if x is None or b is None:
        return {}
    return {op.output("Y")[0]: _like(x),
            op.output("State")[0]: _like(
                x, [x.shape[0], b.shape[-1], x.shape[-1]], "float32")}


@register_op("ssd_prefill", no_grad_slots=["Length"],
             infer_shape=_ssd_prefill_infer)
def _ssd_prefill(ctx):
    """A prompt through one Mamba-2 layer's recurrence. X [n, S, H * P],
    Dt [n, S, H] (raw), B, C [n, S, N], ALog, DtBias, D [H], Length [n]
    int; attr ``chunk``. Y [n, S, H * P] at X's width (the D term
    included) and State [n, N, H * P] float32, the state after row
    Length - 1: rows at and beyond Length have dt 0, so a prompt padded
    to its bucket leaves the state its last real token left."""
    x, dt_raw = ctx.input("X"), ctx.input("Dt")
    n, s, heads = dt_raw.shape
    dt, a = step_inputs(dt_raw, ctx.input("ALog"), ctx.input("DtBias"))
    live = jnp.arange(s)[None, :] < ctx.input("Length").reshape(n, 1)
    dt = jnp.where(live[..., None], dt, 0.0)
    chunk = int(ctx.attr("chunk", 256))
    _count_ssm_site(ctx, "ssd_prefill", "chunked", chunk, heads)
    xh = x.reshape(n, s, heads, -1)
    y, state = ssd_chunked(xh, dt, a, ctx.input("B"), ctx.input("C"),
                           chunk)
    y = y + ctx.input("D").astype(jnp.float32)[:, None] \
        * xh.astype(jnp.float32)
    ctx.set_output("Y", y.reshape(x.shape).astype(x.dtype))
    ctx.set_output("State", state)


def _update_kernel_serves(ctx, state):
    """Whether a ssm_state_update site takes the Pallas kernel, decided
    on what the trace can observe, in the idiom of ops/cache_ops.py
    _append_kernel_lane_axis: a TPU backend, no mesh, a state the
    kernel can serve, and a step program being traced (the build's
    shape inference carries none and reads the shapes off the
    composition). There is no knob."""
    from .pallas.ssm_state_update import fits
    return (jax.default_backend() == "tpu"
            and ctx.extra.get("mesh") is None
            and "program" in ctx.extra
            and fits(state.shape, state.dtype))


@register_op("ssm_state_update", infer_shape=_state_passthrough_infer)
def _ssm_state_update(ctx):
    """One token a slot through one layer's recurrence, the state
    updated in place. State [slots, N, H * P] float32, X [slots, 1,
    H * P], Dt [slots, 1, H] (raw), B, C [slots, 1, N], ALog, DtBias,
    D [H]. Y [slots, 1, H * P] at X's width; StateOut is State's name.
    A slot with no request in it is advanced like any other: its state
    means nothing until a prefill overwrites it."""
    state, x = ctx.input("State"), ctx.input("X")
    slots, _, heads = ctx.input("Dt").shape
    dt, a = step_inputs(ctx.input("Dt").reshape(slots, heads),
                        ctx.input("ALog"), ctx.input("DtBias"))
    width = x.shape[-1] // heads
    xf = x.reshape(slots, -1).astype(jnp.float32)
    decay = jnp.repeat(jnp.exp(dt * a), width, axis=1)      # [slots, HP]
    dx = jnp.repeat(dt, width, axis=1) * xf
    b = ctx.input("B").reshape(slots, -1).astype(jnp.float32)
    c = ctx.input("C").reshape(slots, -1).astype(jnp.float32)
    if _update_kernel_serves(ctx, state):
        from .pallas.ssm_state_update import ssm_state_update
        _count_ssm_site(ctx, "ssm_state_update", "kernel", 0, heads)
        new, y = ssm_state_update(state, decay, dx, b, c)
    else:
        _count_ssm_site(ctx, "ssm_state_update", "composed", 0, heads)
        new = state * decay[:, None, :] + b[:, :, None] * dx[:, None, :]
        y = jnp.einsum("snl,sn->sl", new, c)
    y = y + jnp.repeat(ctx.input("D").astype(jnp.float32), width) * xf
    ctx.set_output("Y", y.reshape(x.shape).astype(x.dtype))
    ctx.set_output("StateOut", new.astype(state.dtype))


# -- the convolution's window --------------------------------------------

def _causal_conv1d_infer(block_desc, op):
    x, w = _var(block_desc, op, "X"), _var(block_desc, op, "W")
    if x is None or w is None:
        return {}
    return {op.output("Out")[0]: _like(x),
            op.output("State")[0]: _like(
                x, [x.shape[0], (w.shape[0] - 1) * x.shape[-1]])}


@register_op("causal_conv1d", no_grad_slots=["Length"],
             infer_shape=_causal_conv1d_infer)
def _causal_conv1d(ctx):
    """Depthwise causal convolution of a prompt. X [n, S, C], W [K, C]
    (tap k multiplies the input K - 1 - k rows back), Bias [C],
    Length [n] int. Out [n, S, C]: a sum of K shifted products in
    float32, at X's width; State [n, (K - 1) * C]: the last K - 1 REAL
    inputs, oldest first, zeros where the prompt is shorter."""
    x, w = ctx.input("X"), ctx.input("W").astype(jnp.float32)
    n, s, ch = x.shape
    taps = w.shape[0]
    _count_ssm_site(ctx, "causal_conv1d", "composed")
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = ctx.input("Bias").astype(jnp.float32) + sum(
        w[k] * xp[:, k:k + s].astype(jnp.float32) for k in range(taps))
    ctx.set_output("Out", out.astype(x.dtype))
    length = ctx.input("Length").reshape(n).astype(jnp.int32)
    # input t sits at row t + K - 1 of xp: rows Length .. Length + K - 2
    # are inputs Length - K + 1 .. Length - 1
    window = jax.vmap(lambda rows, at: jax.lax.dynamic_slice(
        rows, (at, 0), (taps - 1, ch)))(xp, length)
    ctx.set_output("State", window.reshape(n, (taps - 1) * ch))


@register_op("conv_state_update", infer_shape=_state_passthrough_infer)
def _conv_state_update(ctx):
    """One token a slot through the convolution, its window updated in
    place. State [slots, (K - 1) * C] (oldest input first), X [slots,
    1, C], W [K, C], Bias [C]. Out [slots, 1, C]; StateOut is State's
    name: the window moved on by one input."""
    state, x = ctx.input("State"), ctx.input("X")
    w = ctx.input("W").astype(jnp.float32)
    slots, _, ch = x.shape
    taps = w.shape[0]
    _count_ssm_site(ctx, "conv_state_update", "composed")
    new = x.reshape(slots, ch)
    rows = [state[:, k * ch:(k + 1) * ch] for k in range(taps - 1)] + [new]
    out = ctx.input("Bias").astype(jnp.float32) + sum(
        w[k] * rows[k].astype(jnp.float32) for k in range(taps))
    ctx.set_output("Out", out.reshape(x.shape).astype(x.dtype))
    ctx.set_output("StateOut", jnp.concatenate(
        [r.astype(state.dtype) for r in rows[1:]], axis=1))


def _slot_write_infer(block_desc, op):
    st = _var(block_desc, op, "State")
    return {} if st is None else {op.output("StateOut")[0]: _like(st)}


@register_op("slot_state_write", no_grad_slots=["Slot"],
             infer_shape=_slot_write_infer)
def _slot_state_write(ctx):
    """Prefill: one request's state into its slot, whole. State
    [slots, ...], New [1, ...], Slot [1] int; StateOut is State's name.
    Nothing of what the slot held before is left."""
    state = ctx.input("State")
    slot = ctx.input("Slot").reshape(()).astype(jnp.int32)
    ctx.set_output("StateOut", jax.lax.dynamic_update_slice(
        state, ctx.input("New").astype(state.dtype),
        (slot,) + (0,) * (state.ndim - 1)))
