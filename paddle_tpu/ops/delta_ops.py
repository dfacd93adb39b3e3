"""Gated delta-rule linear attention for a token server (Gated DeltaNet,
Yang et al., arXiv:2412.06464, with the negative eigenvalues of
Grazzi et al., arXiv:2411.12537; serving/generation,
models/delta_hybrid.py).

A head keeps one ``[d_k, d_v]`` float32 matrix a sequence. At position
t, with the head's key and query L2-normalised (the query also times
``d_k ** -0.5``), ``alpha_t = exp(-exp(A_log) softplus(a_t + dt_bias))``
in (0, 1) and ``beta_t = beta_scale sigmoid(b_t)`` (``beta_scale`` 2
lets a write flip the sign of what the state holds along the key):

    S <- alpha_t S;  r = S^T k_t;  d = beta_t (v_t - r);
    S <- S + k_t d^T;  o_t = S^T q_t

The state is READ (r) before it is written, and what is written is a
rank-one correction of what was read: a delta rule, not a state-space
scan with other numbers (ops/ssm_ops.py: its write does not depend on
the state).

The state is held ``[.., d_k, heads * d_v]``, as ops/ssm_ops.py holds
its own and for its reason: everything that varies by head and value
column (v, alpha, beta, d, o) is a dense row along the lanes; a head's
key and query lie along the sublanes.

``gated_delta_prefill`` runs a whole prompt in chunks (the paper's WY /
UT form): with G the running sum of log alpha inside a chunk and S_0 the
state the chunk starts from, the chunk's corrections solve

    (I + tril(beta (K K^T) * exp(G_i - G_j), -1)) D
        = beta (V - exp(G) (K S_0))

one unit-triangular solve a chunk a head, and ``O = exp(G) (Q S_0) +
tril((Q K^T) * exp(G_i - G_j)) D``, ``S_C = exp(G_C) S_0 + (K exp(G_C -
G))^T D`` carry the state between chunks under ``lax.scan``. Everything
is float32 and every product runs at HIGHEST precision: the chunked form
equals the recurrence to float32 rounding (tests/test_delta_ops.py), on
a TPU too, and a prompt's state is the one its decode steps continue. A
row at or past ``Length`` takes alpha 1 and beta 0: it neither decays
nor writes. ``gated_delta_state_update`` is one token a slot against
the persistable state, in place: one Pallas call a layer on a TPU
(ops/pallas/delta_state_update.py), the jax.numpy composition elsewhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .ssm_ops import _like, _var

HIGHEST = jax.lax.Precision.HIGHEST


def _count_delta_site(ctx, op, path, chunk=0):
    """One count a delta-rule site traced into a step program, in the
    idiom of ops/ssm_ops.py _count_ssm_site (the build's shape
    inference carries no program and is no site)."""
    if "program" not in ctx.extra:
        return
    from ..observability.registry import default_registry
    default_registry().counter(
        "paddle_tpu_delta_sites_total",
        "Gated delta-rule sites traced into a step program, by op "
        "(gated_delta_prefill, gated_delta_state_update), by the path "
        "taken (chunked: the chunked form of a prompt; kernel: the "
        "Pallas state update of a decode step; composed: jax.numpy left "
        "to XLA) and by the chunk length (0 where none applies).",
        ("op", "path", "chunk")).labels(
            op=op, path=path, chunk=str(chunk)).inc()


def unit_rows(x, heads, eps, scale=1.0):
    """[.., heads * w] -> [.., heads, w] float32, every head's row
    divided by ``sqrt(|row|^2 + eps)`` and times ``scale``."""
    xh = x.astype(jnp.float32).reshape(x.shape[:-1] + (heads, -1))
    return xh * (jax.lax.rsqrt(jnp.sum(xh * xh, -1, keepdims=True) + eps)
                 * scale)


def gates(a_raw, b_raw, a_log, dt_bias, beta_scale):
    """(log alpha, beta) [.., H] float32 of the raw projections a, b."""
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        a_raw.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    return g, beta_scale * jax.nn.sigmoid(b_raw.astype(jnp.float32))


def step_inputs(ctx, rows):
    """What both ops make of their inputs, ``rows`` the leading shape
    ([n, S] or [slots]): (q, k [.., H, d_k], v [.., H, d_v], log alpha,
    beta [.., H]), float32."""
    heads = ctx.input("A").shape[-1]
    eps = float(ctx.attr("norm_eps", 1e-6))
    q, k = (ctx.input(s).reshape(rows + (-1,)) for s in ("Q", "K"))
    d_k = q.shape[-1] // heads
    g, beta = gates(ctx.input("A").reshape(rows + (heads,)),
                    ctx.input("B").reshape(rows + (heads,)),
                    ctx.input("ALog"), ctx.input("DtBias"),
                    float(ctx.attr("beta_scale", 2.0)))
    v = ctx.input("V").astype(jnp.float32).reshape(rows + (heads, -1))
    return (unit_rows(q, heads, eps, d_k ** -0.5),
            unit_rows(k, heads, eps), v, g, beta)


# -- a prompt --------------------------------------------------------------

def delta_chunked(q, k, v, g, beta, initial, chunk):
    """One batch of sequences by the chunked form. q, k [n, S, H, K],
    v [n, S, H, V], g (log alpha) and beta [n, S, H], initial [n, K, H,
    V], all float32; a row that is no token has g 0 and beta 0. Returns
    (o [n, S, H, V], the state after the last row [n, K, H, V]). S is
    padded to a whole number of chunks with such rows."""
    n, s, h, _ = q.shape
    c = min(int(chunk), s)
    pad = -s % c
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),)
                                    * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
    nc = (s + pad) // c

    def by_chunk(t):              # [n, S, H, ..] -> [nc, n, H, c, ..]
        t = t.reshape((n, nc, c) + t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 3, 2), 1, 0)

    q, k, v = by_chunk(q), by_chunk(k), by_chunk(v)
    beta = by_chunk(beta[..., None])                       # [..,c,1]
    cum = jnp.cumsum(by_chunk(g[..., None]), axis=3)       # [..,c,1]
    # row i reads row j through exp(G_i - G_j), j <= i
    rel = cum - jnp.swapaxes(cum, 3, 4)                    # [..,c,c]
    seen = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(seen, rel, -jnp.inf))
    kk = jnp.einsum("znhik,znhjk->znhij", k, k, precision=HIGHEST)
    system = jnp.where(jnp.tril(seen, -1), beta * kk * decay, 0.0) \
        + jnp.eye(c, dtype=jnp.float32)
    # D = U - W S_0: the corrections of a chunk that starts from zero,
    # and what each key column of S_0 takes off them
    rhs = jnp.concatenate([beta * v, beta * jnp.exp(cum) * k], axis=-1)
    solved = jax.lax.linalg.triangular_solve(
        system, rhs, left_side=True, lower=True, unit_diagonal=True)
    u, w = solved[..., :v.shape[-1]], solved[..., v.shape[-1]:]
    qk = jnp.einsum("znhik,znhjk->znhij", q, k, precision=HIGHEST) * decay
    to_end = jnp.exp(cum[..., -1:, :] - cum) * k           # [..,c,K]
    whole = jnp.exp(cum[..., -1, :])                       # [..,1]

    def carry(state, inp):        # state [n, H, K, V]
        q_c, u_c, w_c, qk_c, to_end_c, lead_c, whole_c = inp
        d = u_c - jnp.einsum("nhik,nhkv->nhiv", w_c, state,
                             precision=HIGHEST)
        o = lead_c * jnp.einsum("nhik,nhkv->nhiv", q_c, state,
                                precision=HIGHEST) \
            + jnp.einsum("nhij,nhjv->nhiv", qk_c, d, precision=HIGHEST)
        new = whole_c[..., None] * state + jnp.einsum(
            "nhik,nhiv->nhkv", to_end_c, d, precision=HIGHEST)
        return new, o

    final, o = jax.lax.scan(
        carry, jnp.moveaxis(initial, 2, 1),
        (q, u, w, qk, to_end, jnp.exp(cum), whole))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)          # [n,nc,c,H,V]
    return (o.reshape(n, nc * c, h, -1)[:, :s],
            jnp.moveaxis(final, 1, 2))


def _prefill_infer(block_desc, op):
    q, v = _var(block_desc, op, "Q"), _var(block_desc, op, "V")
    a = _var(block_desc, op, "A")
    if q is None or v is None or a is None:
        return {}
    d_k = q.shape[-1] // a.shape[-1]
    return {op.output("Out")[0]: _like(v),
            op.output("State")[0]: _like(
                v, [v.shape[0], d_k, v.shape[-1]], "float32")}


@register_op("gated_delta_prefill", no_grad_slots=["Length"],
             infer_shape=_prefill_infer)
def _gated_delta_prefill(ctx):
    """A prompt through one layer's delta rule. Q, K [n, S, H * d_k],
    V [n, S, H * d_v], A, B [n, S, H] (raw), ALog, DtBias [H], Length
    [n] int, Initial [n, d_k, H * d_v] float32 (optional: zeros); attrs
    ``chunk``, ``beta_scale``, ``norm_eps``. Out [n, S, H * d_v] at V's
    width and State [n, d_k, H * d_v] float32, the state after row
    Length - 1: rows at and beyond Length neither decay nor write, so a
    prompt padded to its bucket leaves the state its last real token
    left."""
    v_in = ctx.input("V")
    n, s = v_in.shape[:2]
    q, k, v, g, beta = step_inputs(ctx, (n, s))
    heads, d_k = q.shape[2:]
    live = (jnp.arange(s)[None, :]
            < ctx.input("Length").reshape(n, 1))[..., None]
    g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    chunk = int(ctx.attr("chunk", 64))
    _count_delta_site(ctx, "gated_delta_prefill", "chunked", chunk)
    initial = ctx.input("Initial")
    initial = jnp.zeros((n, d_k, heads, v.shape[-1]), jnp.float32) \
        if initial is None else initial.astype(jnp.float32).reshape(
            n, d_k, heads, -1)
    o, state = delta_chunked(q, k, v, g, beta, initial, chunk)
    ctx.set_output("Out", o.reshape(v_in.shape).astype(v_in.dtype))
    ctx.set_output("State", state.reshape(n, d_k, -1))


# -- one token a slot --------------------------------------------------------

def _update_kernel_serves(ctx, state, heads):
    """Whether a gated_delta_state_update site takes the Pallas kernel,
    decided on what the trace can observe (ops/ssm_ops.py
    _update_kernel_serves): a TPU backend, no mesh, a state the kernel
    can serve, and a step program being traced. There is no knob."""
    from .pallas.delta_state_update import fits
    return (jax.default_backend() == "tpu"
            and ctx.extra.get("mesh") is None
            and "program" in ctx.extra
            and fits(state.shape, state.dtype, heads))


def delta_step(state, q, k, v, alpha, beta):
    """The recurrence's one step as jax.numpy: state [slots, K, H, V],
    q, k [slots, H, K], v [slots, H, V], alpha, beta [slots, H], all
    float32 -> (the new state, o [slots, H, V])."""
    decayed = state * alpha[:, None, :, None]
    r = jnp.einsum("skhv,shk->shv", decayed, k, precision=HIGHEST)
    d = beta[..., None] * (v - r)
    new = decayed + jnp.einsum("shk,shv->skhv", k, d, precision=HIGHEST)
    return new, jnp.einsum("skhv,shk->shv", new, q, precision=HIGHEST)


def _update_infer(block_desc, op):
    v, st = _var(block_desc, op, "V"), _var(block_desc, op, "State")
    if v is None or st is None:
        return {}
    return {op.output("Out")[0]: _like(v),
            op.output("StateOut")[0]: _like(st)}


@register_op("gated_delta_state_update", infer_shape=_update_infer)
def _gated_delta_state_update(ctx):
    """One token a slot through one layer's delta rule, the state
    updated in place. State [slots, d_k, H * d_v] float32, Q, K [slots,
    1, H * d_k], V [slots, 1, H * d_v], A, B [slots, 1, H] (raw), ALog,
    DtBias [H]; attrs ``beta_scale``, ``norm_eps``. Out [slots, 1,
    H * d_v] at V's width; StateOut is State's name. A slot
    with no request in it is advanced like any other: its state means
    nothing until a prefill overwrites it."""
    state, x = ctx.input("State"), ctx.input("V")
    slots = x.shape[0]
    q, k, v, g, beta = step_inputs(ctx, (slots,))
    heads = q.shape[1]
    alpha = jnp.exp(g)
    if _update_kernel_serves(ctx, state, heads):
        from .pallas.delta_state_update import delta_state_update
        _count_delta_site(ctx, "gated_delta_state_update", "kernel")
        new, o = delta_state_update(state, q, k, v.reshape(slots, -1),
                                    alpha, beta)
    else:
        _count_delta_site(ctx, "gated_delta_state_update", "composed")
        new, o = delta_step(
            state.astype(jnp.float32).reshape(slots, state.shape[1],
                                              heads, -1),
            q, k, v, alpha, beta)
        new = new.reshape(state.shape)
    ctx.set_output("Out", o.reshape(x.shape).astype(x.dtype))
    ctx.set_output("StateOut", new.astype(state.dtype))
