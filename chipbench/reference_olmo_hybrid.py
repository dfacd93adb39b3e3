"""Plain reference of the ``olmo-hybrid-7b`` configuration: the forward
pass of the published block stack in ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``, with nothing imported from
the program under test.

It is written the slow, obvious way on purpose. The delta rule of a
linear-attention layer is run AS WRITTEN, one position at a time under
``lax.scan`` — no chunks, no triangular solve, no cache, no carried
window: a head's state ``S in R^(d_k x d_v)`` obeys

    S <- alpha_t S;  r = S^T k_t;  d = beta_t (v_t - r);
    S <- S + k_t d^T;  o_t = S^T q_t

with ``q_t``, ``k_t`` the head's rows after the convolution and SiLU
divided by ``sqrt(|row|^2 + 1e-6)`` (the query also times ``d_k **
-0.5``), ``alpha_t = exp(-exp(A_log) softplus(a_t + dt_bias))`` and
``beta_t = 2 sigmoid(b_t)``; each of the three convolutions is a sum of
4 shifted products; a full layer's attention is scores-softmax-product
over the whole causal square at ``128 ** -0.5``, after an RMS norm over
the whole query and key projections, with no position term. A block is
``h = x + RMS(Mixer(x); w1)`` then ``x' = h + RMS(W_down(silu(W_gate h) *
(W_up h)); w2)``; the input is ``E[token]`` and the logits are ``RMS(x;
w_f) W_head`` (the head is NOT the embedding).

The weights are a TAPE: the arrays in the order the program created its
parameters (the embedding; a layer: the mixer's arrays, its norm's
scale, the FFN's gate, up and down, its norm's scale; the last norm's
scale; the head), at whatever width they are stored — they are widened
to float32 here, which is exact. They are taken from the host a layer
at a time and every batch of rows passes through a layer before the
next is uploaded, so a float32 copy of the stack never lies on the
device beside the engine's own arrays.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# the arrays a layer of each kind has on the tape, in order
LINEAR_ARRAYS = ("w_q", "taps_q", "w_k", "taps_k", "w_v", "taps_v", "w_a",
                 "w_b", "a_log", "dt_bias", "w_g", "norm_o", "w_o",
                 "norm1", "gate", "up", "down", "norm2")
FULL_ARRAYS = ("w_q", "norm_q", "w_k", "norm_k", "w_v", "w_o", "norm1",
               "gate", "up", "down", "norm2")
L2_EPS = 1e-6
# positions a call of the head holds at once: [positions, vocab] f32
HEAD_CHUNK = 2048


def layers_of(tape: list, layer_types) -> tuple:
    """(embedding, [dict of one layer's arrays], final norm scale, the
    head)."""
    at, out = 1, []
    for kind in layer_types:
        names = LINEAR_ARRAYS if kind == "linear_attention" \
            else FULL_ARRAYS
        out.append(dict(zip(names, tape[at:at + len(names)])))
        at += len(names)
    if at + 2 != len(tape):
        raise ValueError(f"the tape holds {len(tape)} arrays; the stack "
                         f"reads {at + 2}")
    return tape[0], out, tape[at], tape[at + 1]


def _f32(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(a).astype(jnp.float32), tree)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _conv(t, taps):
    """Depthwise causal convolution, no bias: tap j reads the input
    len(taps) - 1 - j rows back."""
    n = taps.shape[0]
    s = t.shape[1]
    padded = jnp.pad(t, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(taps[j] * padded[:, j:j + s] for j in range(n))


def _unit(t):
    return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + L2_EPS)


def linear_mixer(u, w, arch):
    """u [rows, S, d] -> (the mixer's output, the state after the last
    row [rows, H, d_k, d_v], the three convolutions' inputs)."""
    heads = arch["linear_num_value_heads"]
    d_k, d_v = arch["linear_key_head_dim"], arch["linear_value_head_dim"]
    rows, s, _ = u.shape
    raw = {n: u @ w["w_" + n] for n in "qkv"}
    q, k, v = (_silu(_conv(raw[n], w["taps_" + n])) for n in "qkv")
    q = _unit(q.reshape(rows, s, heads, d_k)) * d_k ** -0.5
    k = _unit(k.reshape(rows, s, heads, d_k))
    v = v.reshape(rows, s, heads, d_v)
    alpha = jnp.exp(-jnp.exp(w["a_log"])
                    * jax.nn.softplus(u @ w["w_a"] + w["dt_bias"]))
    beta = jax.nn.sigmoid(u @ w["w_b"])
    if arch["linear_allow_neg_eigval"]:
        beta = 2.0 * beta

    def step(state, inp):                  # state [rows, H, d_k, d_v]
        q_t, k_t, v_t, alpha_t, beta_t = inp
        state = alpha_t[..., None, None] * state
        r = jnp.einsum("rhkv,rhk->rhv", state, k_t)
        d = beta_t[..., None] * (v_t - r)
        state = state + k_t[..., :, None] * d[..., None, :]
        return state, jnp.einsum("rhkv,rhk->rhv", state, q_t)

    final, o = jax.lax.scan(
        step, jnp.zeros((rows, heads, d_k, d_v), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, alpha, beta)),
        unroll=8)
    o = _rms(jnp.moveaxis(o, 0, 1), w["norm_o"], arch["rms_norm_eps"])
    z = (u @ w["w_g"]).reshape(rows, s, heads, d_v)
    return (o * _silu(z)).reshape(rows, s, heads * d_v) @ w["w_o"], \
        final, raw


def full_mixer(u, w, arch):
    """u [rows, S, d] -> (the mixer's output, keys, values [rows, key
    heads, S, width])."""
    heads, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    rows, s, d = u.shape
    width = d // heads
    eps = arch["rms_norm_eps"]

    def split(t, h):
        return t.reshape(rows, s, h, width).transpose(0, 2, 1, 3)

    q = split(_rms(u @ w["w_q"], w["norm_q"], eps), heads)
    keys = split(_rms(u @ w["w_k"], w["norm_k"], eps), kv)
    values = split(u @ w["w_v"], kv)
    k = jnp.repeat(keys, heads // kv, axis=1)
    v = jnp.repeat(values, heads // kv, axis=1)
    seen = jnp.tril(jnp.ones((s, s), bool))

    def one(qkv):                      # a row at a time: [h, S, S] scores
        q1, k1, v1 = qkv
        scores = jnp.einsum("hqd,hkd->hqk", q1, k1) * width ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("hqk,hkd->hqd", probs, v1)

    o = jax.lax.map(one, (q, k, v))
    return o.transpose(0, 2, 1, 3).reshape(rows, s, d) @ w["w_o"], \
        keys, values


@functools.partial(jax.jit, static_argnames=("kind", "arch", "with_state"))
def _layer(x, w, kind, arch, with_state=False):
    """One block; ``with_state`` also hands back what a server would
    keep of these rows: a linear layer's state after the last row and
    the last taps - 1 inputs of each convolution, a full layer's keys
    and values."""
    arch = dict(arch)
    eps = arch["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        if kind == "linear_attention":
            mixed, final, raw = linear_mixer(x, w, arch)
            last = 1 - arch["linear_conv_kernel_dim"]
            kept = (final, {n: t[:, last:] for n, t in raw.items()})
        else:
            mixed, k, v = full_mixer(x, w, arch)
            kept = (k, v)
        h = x + _rms(mixed, w["norm1"], eps)
        out = h + _rms((_silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"],
                       w["norm2"], eps)
        return (out, kept) if with_state else out


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_gap(x, head, scale, chosen, eps):
    """For positions x [m, d]: the best logit less the logit of
    ``chosen`` [m]."""
    with jax.default_matmul_precision("highest"):
        logits = _rms(x, scale.astype(jnp.float32), eps) \
            @ head.astype(jnp.float32)
        picked = jnp.take_along_axis(logits, chosen[:, None], 1)[:, 0]
        return jnp.max(logits, -1) - picked


def _static(arch) -> tuple:
    """The numbers a layer reads, hashable for jit."""
    keys = ("linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim",
            "linear_allow_neg_eigval", "num_attention_heads",
            "num_key_value_heads", "rms_norm_eps")
    return tuple((k, arch[k]) for k in keys)


def hidden_states(tape: list, batches: list, arch: dict,
                  with_state: bool = False):
    """The stack's output before the last norm, [rows, S, d] float32,
    for each int token array [rows, S] of ``batches``; ``with_state``
    adds, for each batch, what every layer would keep of it (see
    ``_layer``)."""
    table, layers, _, _ = layers_of(tape, arch["layer_types"])
    static = _static(arch)
    table = jnp.asarray(table)
    xs = [jnp.take(table, jnp.asarray(t, jnp.int32), axis=0)
          .astype(jnp.float32) for t in batches]
    kept = [[] for _ in batches]
    for kind, w in zip(arch["layer_types"], layers):
        w = {k: jnp.asarray(v) for k, v in w.items()}     # one upload
        outs = [_layer(x, w, kind, static, with_state) for x in xs]
        # the next layer's upload waits for this layer's work: ahead of
        # it, dispatch would queue every layer's weights on the device
        jax.block_until_ready(outs)
        if with_state:
            for held, (_, k) in zip(kept, outs):
                held.append(k)
            outs = [x for x, _ in outs]
        xs = outs
    return (xs, kept) if with_state else xs


def states(tape: list, tokens, arch: dict) -> list:
    """What a server would keep after reading ``tokens`` [rows, S], a
    layer at a time: for a linear layer (state [rows, H, d_k, d_v] after
    the last row, {"q", "k", "v": each convolution's last taps - 1
    inputs [rows, taps - 1, columns]}), for a full layer (keys, values
    [rows, key heads, S, width])."""
    return hidden_states(tape, [np.asarray(tokens)], arch, True)[1][0]


def rates(tape: list, arch: dict) -> dict:
    """{linear layer's index: [H] float32}: what a head forgets of its
    state a step when its projection adds nothing to the step's bias,
    ``softplus(dt_bias) exp(A_log)`` — the heads near 0.001 keep a
    thousand steps, the heads near 1 two."""
    _, layers, _, _ = layers_of(tape, arch["layer_types"])
    return {i: np.logaddexp(0.0, np.asarray(w["dt_bias"], np.float32))
            * np.exp(np.asarray(w["a_log"], np.float32))
            for i, w in enumerate(layers) if "a_log" in w}


def logits(tape: list, tokens, arch: dict) -> np.ndarray:
    """[rows, S, vocab] float32: every position's logits (small sizes:
    the tests)."""
    _, _, final, head = layers_of(tape, arch["layer_types"])
    x = hidden_states(tape, [tokens], arch)[0]
    with jax.default_matmul_precision("highest"):
        out = _rms(x, jnp.asarray(final, jnp.float32),
                   arch["rms_norm_eps"]) \
            @ jnp.asarray(head).astype(jnp.float32)
    return np.asarray(out)


def choice_gaps(tape: list, batches: list, arch: dict) -> list:
    """For each [rows, S] token array: [rows, S] float32, how far the
    NEXT token's logit at each position lies below the best logit there
    (0 where the row continues with the reference's own greedy choice;
    the last column means nothing)."""
    _, _, final, head = layers_of(tape, arch["layer_types"])
    head, scale = jnp.asarray(head), jnp.asarray(final)
    eps = float(arch["rms_norm_eps"])
    out = []
    for tokens, x in zip(batches, hidden_states(tape, batches, arch)):
        rows, s = tokens.shape
        nxt = np.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        flat = x.reshape(rows * s, -1)
        chosen = jnp.asarray(nxt.reshape(-1), jnp.int32)
        step = min(HEAD_CHUNK, rows * s)
        gaps = [_head_gap(flat[i:i + step], head, scale,
                          chosen[i:i + step], eps)
                for i in range(0, rows * s, step)]
        out.append(np.asarray(jnp.concatenate(gaps)).reshape(rows, s))
    return out
