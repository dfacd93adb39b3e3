"""Plain reference of the ``granite-4p0-h-micro`` configuration: the
forward pass of the published block stack in ``jax.numpy``, float32,
under ``jax.default_matmul_precision("highest")``, with nothing
imported from the program under test.

It is written the slow, obvious way on purpose. The recurrence of a
Mamba-2 layer is run AS WRITTEN, one position at a time under
``lax.scan`` — no chunks, no cache, no carried window: a head's state
``S in R^(d_head x d_state)`` obeys ``S_t = exp(dt_t A) S_(t-1) + dt_t
x_t B_t^T`` and ``y_t = S_t C_t + D x_t``; the convolution is a sum of
``d_conv`` shifted products; attention is scores-softmax-product over
the whole causal square with the published ``attention_multiplier``
and no position term. Every layer is ``x += r Mixer(RMS(x; w1))`` then
``x += r W_down(silu(W_gate v) * (W_up v))``, ``v = RMS(x; w2)``; the
input is ``embedding_multiplier E[token]`` and the logits are ``E^T
RMS(x; w_f) / logits_scaling`` (the embedding is tied).

The weights are a TAPE: the arrays in the order the program created its
parameters (the embedding; a layer: its first norm's scale, the mixer's
arrays, its second norm's scale, the FFN's gate, up and down; the last
norm's scale), at whatever width they are stored — they are widened to
float32 here, which is exact. They are taken from the host a layer at a
time and every batch of rows passes through a layer before the next is
uploaded, so the 12.8 GB of a float32 copy never lie on the device
beside the engine's own arrays.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# how many arrays a layer of each kind has on the tape, its two norms
# and its FFN included
MAMBA_ARRAYS = ("norm1", "conv_w", "conv_b", "a_log", "dt_bias", "d",
                "w_in", "norm_g", "w_out", "norm2", "gate", "up", "down")
ATTENTION_ARRAYS = ("norm1", "w_q", "w_k", "w_v", "w_o", "norm2", "gate",
                    "up", "down")
# positions a call of the head holds at once: [positions, vocab] f32
HEAD_CHUNK = 2048


def layers_of(tape: list, layer_types) -> tuple:
    """(embedding, [dict of one layer's arrays], final norm scale)."""
    at, out = 1, []
    for kind in layer_types:
        names = MAMBA_ARRAYS if kind == "mamba" else ATTENTION_ARRAYS
        out.append(dict(zip(names, tape[at:at + len(names)])))
        at += len(names)
    if at + 1 != len(tape):
        raise ValueError(f"the tape holds {len(tape)} arrays; the stack "
                         f"reads {at + 1}")
    return tape[0], out, tape[at]


def _f32(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(a).astype(jnp.float32), tree)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _ffn(x, w, arch):
    v = _rms(x, w["norm2"], arch["rms_norm_eps"])
    return x + arch["residual_multiplier"] * (
        (_silu(v @ w["gate"]) * (v @ w["up"])) @ w["down"])


def mamba_mixer(u, w, arch):
    """u [rows, S, d] -> the mixer's output; also returns the state
    after the last row [rows, H, P, N] and the convolution's inputs."""
    heads, width = arch["mamba_n_heads"], arch["mamba_d_head"]
    n, taps = arch["mamba_d_state"], arch["mamba_d_conv"]
    inner = heads * width
    rows, s, _ = u.shape
    z, xbc, dt = jnp.split(u @ w["w_in"], [inner, 2 * inner + 2 * n], -1)
    # the convolution: tap k reads the input taps - 1 - k rows back
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = w["conv_b"] + sum(w["conv_w"][k] * padded[:, k:k + s]
                             for k in range(taps))
    x, b, c = jnp.split(_silu(conv), [inner, inner + n], -1)
    x = x.reshape(rows, s, heads, width)
    dt = jax.nn.softplus(dt + w["dt_bias"])               # [rows, S, H]
    a = -jnp.exp(w["a_log"])

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp      # [rows,H,P] [rows,H] [rows,N] x2
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return state, jnp.einsum("rhpn,rn->rhp", state, c_t)

    # unroll: eight positions a trip of the loop, each still computed
    # from the one before it
    final, y = jax.lax.scan(
        step, jnp.zeros((rows, heads, width, n), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)), unroll=8)
    y = jnp.moveaxis(y, 0, 1) + w["d"][:, None] * x       # [rows,S,H,P]
    g = y.reshape(rows, s, inner) * _silu(z)
    return _rms(g, w["norm_g"], arch["rms_norm_eps"]) @ w["w_out"], \
        final, xbc


def attention_mixer(u, w, arch):
    """u [rows, S, d] -> (the mixer's output, keys, values at the key
    heads [rows, kv, S, width])."""
    heads, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    rows, s, d = u.shape
    width = d // heads

    def split(t, h):
        return t.reshape(rows, s, h, width).transpose(0, 2, 1, 3)

    q = split(u @ w["w_q"], heads)
    keys, values = split(u @ w["w_k"], kv), split(u @ w["w_v"], kv)
    # query head h reads key head h // (heads / kv)
    k = jnp.repeat(keys, heads // kv, axis=1)
    v = jnp.repeat(values, heads // kv, axis=1)
    seen = jnp.tril(jnp.ones((s, s), bool))

    def one(qkv):                      # a row at a time: [h, S, S] scores
        q1, k1, v1 = qkv
        scores = jnp.einsum("hqd,hkd->hqk", q1, k1) \
            * arch["attention_multiplier"]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("hqk,hkd->hqd", probs, v1)

    o = jax.lax.map(one, (q, k, v))
    return o.transpose(0, 2, 1, 3).reshape(rows, s, d) @ w["w_o"], \
        keys, values


@functools.partial(jax.jit, static_argnames=("kind", "arch", "with_state"))
def _layer(x, w, kind, arch, with_state=False):
    """One layer; ``with_state`` also hands back what a server would
    keep of these rows: a mamba layer's state after the last row and
    the last ``d_conv - 1`` inputs of its convolution, an attention
    layer's keys and values at its key heads."""
    arch = dict(arch)
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        u = _rms(x, w["norm1"], arch["rms_norm_eps"])
        if kind == "mamba":
            mixed, final, xbc = mamba_mixer(u, w, arch)
            kept = (final, xbc[:, 1 - arch["mamba_d_conv"]:])
        else:
            mixed, k, v = attention_mixer(u, w, arch)
            kept = (k, v)
        out = _ffn(x + arch["residual_multiplier"] * mixed, w, arch)
        return (out, kept) if with_state else out


@functools.partial(jax.jit, static_argnames=("arch",))
def _head_gap(x, table, scale, chosen, arch):
    """For positions x [m, d]: the best logit less the logit of
    ``chosen`` [m]."""
    arch = dict(arch)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, scale.astype(jnp.float32), arch["rms_norm_eps"])
        logits = h @ table.astype(jnp.float32).T / arch["logits_scaling"]
        picked = jnp.take_along_axis(logits, chosen[:, None], 1)[:, 0]
        return jnp.max(logits, -1) - picked


def _static(arch) -> tuple:
    """The numbers a layer reads, hashable for jit."""
    keys = ("mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "mamba_d_conv", "num_attention_heads", "num_key_value_heads",
            "attention_multiplier", "residual_multiplier", "rms_norm_eps",
            "logits_scaling")
    return tuple((k, arch[k]) for k in keys)


def hidden_states(tape: list, batches: list, arch: dict,
                  with_state: bool = False):
    """The stack's output before the last norm, [rows, S, d] float32,
    for each int token array [rows, S] of ``batches``; ``with_state``
    adds, for each batch, what every layer would keep of it (see
    ``_layer``)."""
    table, layers, _ = layers_of(tape, arch["layer_types"])
    static = _static(arch)
    table = jnp.asarray(table)
    xs = [arch["embedding_multiplier"]
          * jnp.take(table, jnp.asarray(t, jnp.int32), axis=0)
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
    layer at a time: for a mamba layer (state [rows, H, P, N] after the
    last row, the convolution's last d_conv - 1 inputs [rows, d_conv -
    1, C]), for an attention layer (keys, values [rows, key heads, S,
    width])."""
    return hidden_states(tape, [np.asarray(tokens)], arch, True)[1][0]


def rates(tape: list, arch: dict) -> dict:
    """{mamba layer's index: [H] float32}: what a head forgets of its
    state a step when its projection adds nothing to the step's bias,
    ``softplus(dt_bias) exp(A_log)`` — the heads near 0.001 keep a
    thousand steps, the heads near 1 two."""
    _, layers, _ = layers_of(tape, arch["layer_types"])
    return {i: np.logaddexp(0.0, np.asarray(w["dt_bias"], np.float32))
            * np.exp(np.asarray(w["a_log"], np.float32))
            for i, w in enumerate(layers) if "a_log" in w}


def logits(tape: list, tokens, arch: dict) -> np.ndarray:
    """[rows, S, vocab] float32: every position's logits (small sizes:
    the tests)."""
    _, _, final = layers_of(tape, arch["layer_types"])
    x = hidden_states(tape, [tokens], arch)[0]
    with jax.default_matmul_precision("highest"):
        h = _rms(x, jnp.asarray(final, jnp.float32), arch["rms_norm_eps"])
        out = h @ jnp.asarray(tape[0]).astype(jnp.float32).T \
            / arch["logits_scaling"]
    return np.asarray(out)


def choice_gaps(tape: list, batches: list, arch: dict) -> list:
    """For each [rows, S] token array: [rows, S] float32, how far the
    NEXT token's logit at each position lies below the best logit there
    (0 where the row continues with the reference's own greedy choice;
    the last column means nothing)."""
    _, _, final = layers_of(tape, arch["layer_types"])
    static = _static(arch)
    table, scale = jnp.asarray(tape[0]), jnp.asarray(final)
    out = []
    for tokens, x in zip(batches, hidden_states(tape, batches, arch)):
        rows, s = tokens.shape
        nxt = np.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        flat = x.reshape(rows * s, -1)
        chosen = jnp.asarray(nxt.reshape(-1), jnp.int32)
        step = min(HEAD_CHUNK, rows * s)
        gaps = [_head_gap(flat[i:i + step], table, scale,
                          chosen[i:i + step], static)
                for i in range(0, rows * s, step)]
        out.append(np.asarray(jnp.concatenate(gaps)).reshape(rows, s))
    return out
