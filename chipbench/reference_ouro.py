"""Plain reference of the ``ouro-2p6b`` configuration: the forward pass,
its loss and ``jax.grad`` of it in straightforward ``jax.numpy``, float32
under ``default_matmul_precision("highest")``: a PYTHON ``for`` over the
passes and over the layers (no scan, no loop construct), a masked
softmax over the whole score matrix (no kernel). Weights are the
system's own, read as a tape in the order
``paddle_tpu/models/looped_lm.py`` creates them. Nothing is imported
from ``paddle_tpu``; the norm, the gated FFN and the walk over a batch's
sequences are ``reference_joyai``'s and ``reference_laguna``'s own few
lines.

It follows the published config
(https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json) and,
where the config is silent, the model's description ("Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741; ``assumed`` in
the configuration's file has each with its reason):

    x_0 = E[tokens]
    for t = 1..T (total_ut_steps), the SAME weights every t:
        h = x_(t-1)
        for l = 1..L:   h = h + RMS(Attn_l(RMS(h; n1_l)); n2_l)
                        h = h + RMS(FFN_l (RMS(h; n3_l)); n4_l)
        x_t = RMS(h; n_final)               the final norm INSIDE the loop
        z_t = x_t W_head;  lam_t = sigmoid(x_t w_g + b_g)
    p_1 = lam_1;  p_t = lam_t prod_(j<t) (1 - lam_j);  p_T = prod_(j<T) (1 - lam_j)
    loss = mean over tokens of [ sum_t p_t CE(z_t, label) - beta H(p) ]

Attention: 16 query heads over 16 key heads of 128, no bias, no q/k
norm, rotary embedding in the rotate-half layout over the whole head at
theta 1e6, causal; the FFN is SiLU-gated, 5632 wide. Every block is a
``jax.checkpoint`` and the head goes an exit at a time under one, so
that the step's activations fit beside the program's state; neither
changes a number.
"""
from __future__ import annotations

import numpy as np

from .reference_joyai import _Frozen, gated_ffn, rms_norm
from .reference_laguna import _chunks

BLOCK_ARRAYS = 11     # n1, q, k, v, o, n2, n3, gate, up, down, n4
LOG_FLOOR = 1e-20     # log(max(p, this)), as the program's: 0 x log 0 = 0


def rope(x, positions, theta):
    """x [..., S, w]: columns (i, i + w/2) turned by position x
    theta^(-2i/w) (rotate-half, over the whole head)."""
    import jax.numpy as jnp
    w = x.shape[-1]
    f = theta ** (-np.arange(0, w, 2, dtype=np.float64) / w)
    angle = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(f, jnp.float32)[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :w // 2], x[..., w // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(x, positions, w_q, w_k, w_v, w_o, m):
    """x [b, S, d] (normed) -> [b, S, d]."""
    import jax
    import jax.numpy as jnp
    b, s, _ = x.shape
    h, h_kv = int(m["num_attention_heads"]), int(m["num_key_value_heads"])
    d, theta = int(m["head_dim"]), float(m["rope_theta"])

    def heads(t, n):
        return t.reshape(b, s, n, d).transpose(0, 2, 1, 3)

    q = rope(heads(x @ w_q, h), positions, theta)
    k = rope(heads(x @ w_k, h_kv), positions, theta)
    v = heads(x @ w_v, h_kv)
    k, v = (jnp.repeat(t, h // h_kv, axis=1) for t in (k, v))
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return out.transpose(0, 2, 1, 3).reshape(b, s, h * d) @ w_o


def block(h, positions, w, m):
    """One sandwich block; w: n1, q, k, v, o, n2, n3, gate, up, down,
    n4. The SECOND norm of a sub-layer acts on its output, before the
    residual add."""
    eps = float(m["rms_norm_eps"])
    h = h + rms_norm(attention(rms_norm(h, w[0], eps), positions, *w[1:5],
                               m), w[5], eps)
    return h + rms_norm(gated_ffn(rms_norm(h, w[6], eps), *w[7:10]),
                        w[10], eps)


def one_pass(x, positions, stack, m):
    """The L blocks and the final norm: ``stack`` is L blocks' arrays
    and then the final norm's scale."""
    import jax
    layers = int(m["num_hidden_layers"])
    for i in range(layers):
        x = jax.checkpoint(block, static_argnums=(3,))(
            x, positions,
            stack[i * BLOCK_ARRAYS:(i + 1) * BLOCK_ARRAYS], m)
    return rms_norm(x, stack[layers * BLOCK_ARRAYS],
                    float(m["rms_norm_eps"]))


def exit_distribution(lams):
    """[lam_1 .. lam_T] -> [p_1 .. p_T]; sums to 1 a token."""
    import jax.numpy as jnp
    left, out = jnp.ones_like(lams[0]), []
    for lam in lams[:-1]:
        out.append(lam * left)
        left = left * (1.0 - lam)
    return out + [left]


def _token_ce(x, head, labels):
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(x @ head, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


def exit_loss_sum(table, stacks, head, w_g, b_g, tokens, labels,
                  positions, m):
    """The loss summed over positions, pass t through ``stacks[t]``:
    the model hands the SAME stack T times, a test T copies of it."""
    import jax
    import jax.numpy as jnp
    x, ces, lams = table[tokens], [], []
    for stack in stacks:                     # a Python loop: no scan
        x = one_pass(x, positions, stack, m)
        ces.append(jax.checkpoint(_token_ce)(x, head, labels))
        lams.append(jax.nn.sigmoid((x @ w_g)[..., 0] + b_g[0]))
    ps = exit_distribution(lams)
    beta = float(m["exit_entropy_beta"])
    expected = sum(p * ce for p, ce in zip(ps, ces))
    entropy = -sum(p * jnp.log(jnp.maximum(p, LOG_FLOOR)) for p in ps)
    return jnp.sum(expected - beta * entropy)


def split_tape(tape, m):
    """(table, stack, head, w_g, b_g) of the tape."""
    n = int(m["num_hidden_layers"]) * BLOCK_ARRAYS + 1
    assert len(tape) == n + 4, \
        "the parameter tape is not the model's length"
    return tape[0], list(tape[1:1 + n]), tape[1 + n], tape[2 + n], \
        tape[3 + n]


def loss_sum(tape, tokens, labels, positions, m):
    """Integer [b, S] arrays; summed over positions."""
    table, stack, head, w_g, b_g = split_tape(tape, m)
    return exit_loss_sum(table, [stack] * int(m["total_ut_steps"]), head,
                         w_g, b_g, tokens, labels, positions, m)


def loss(tape_arrays, batch: dict, model: dict,
         chunk_tokens: int = 2048) -> float:
    """The training loss on one feed batch ({"trg_ids", "trg_labels"}:
    [b, S, 1] ids = t_i, t_(i+1); "src_ids" is fed and unused): the mean
    over every position, nothing is masked. ``model`` is the
    configuration's builder arguments."""
    sums, count = _chunks(loss_sum, tape_arrays, batch, model,
                          chunk_tokens)
    return sum(float(s) for s in sums) / count


def grads(tape_arrays, batch: dict, model: dict,
          chunk_tokens: int = 2048) -> list:
    """Gradient of that loss with respect to every array of the tape
    (``jax.grad`` of the same plain forward: a shared array's is the sum
    over its T uses). Device arrays, tape order."""
    import jax
    parts, count = _chunks(jax.grad(loss_sum), tape_arrays, batch, model,
                           chunk_tokens)
    total = parts[0]
    for g in parts[1:]:
        total = [a + b for a, b in zip(total, g)]
    return [g / count for g in total]
