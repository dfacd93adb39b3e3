"""Plain reference of the ``joyai-llm-flash`` configuration: the forward
pass, both losses and ``jax.grad`` of them in straightforward
``jax.numpy``, float32 under ``default_matmul_precision("highest")``, no
kernel, no sort, no grouped product. Weights are the system's own, read
as a tape in the order ``paddle_tpu/models/decoder_moe.py`` creates
them.

It follows the published config
(https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json)
with the DeepSeek-V3 layer equations (arXiv:2412.19437; its inference
code for the details the paper leaves open): pre-norm blocks, multi-head
latent attention in its plain form with RoPE on interleaved pairs of the
rotary parts, a dense gated FFN in the leading layer, then sigmoid-scored
top-k routing selected with the correction bias and weighted without it,
a shared expert, and one multi-token-prediction module that shares the
embedding and the head. The same share of the deployment as the program:
only experts [expert_offset, expert_offset + experts_held) are computed,
each for EVERY token and masked by the routing (no dispatch), and the
vocabulary is the slice. Departures from the published description, all
listed under ``assumed`` in the configuration's file: the MTP loss
weight, the order of the MTP concatenation, which hidden state the MTP
module is handed, a selection bias that nothing updates, dropout 0, and
seeded full-length sequences. Attention goes a few heads at a time
(``jax.lax.map``) and every block is a ``jax.checkpoint``, so that 4,096
tokens fit beside the program's state; neither changes a number.
"""
from __future__ import annotations

import numpy as np

HEADS_AT_A_TIME = 4


def rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + eps) * scale


def rope(x, positions, theta):
    """x [..., S, r]: pair (x[2i], x[2i+1]) of the row at position p
    turned by p * theta^(-2i/r)."""
    import jax.numpy as jnp
    r = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32)
                                / r))
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def latent_attention(x, positions, w, m):
    """x [b, S, d]; w: the block's eight attention arrays after its
    input norm (q_a, q_norm, q_b, kv_a, kv_norm, kv_b, o)."""
    import jax
    import jax.numpy as jnp
    q_a, q_norm, q_b, kv_a, kv_norm, kv_b, w_o = w
    b, s, _ = x.shape
    h = int(m["num_attention_heads"])
    nope, rot = int(m["qk_nope_head_dim"]), int(m["qk_rope_head_dim"])
    d_v, eps = int(m["v_head_dim"]), float(m["rms_norm_eps"])
    theta = float(m["rope_theta"])

    def heads(t, width):
        return t.reshape(b, s, h, width).transpose(0, 2, 1, 3)

    q = heads(rms_norm(x @ q_a, q_norm, eps) @ q_b, nope + rot)
    q = jnp.concatenate([q[..., :nope],
                         rope(q[..., nope:], positions, theta)], -1)
    ckv = x @ kv_a
    c, k_rot = ckv[..., :-rot], ckv[..., -rot:]
    kv = heads(rms_norm(c, kv_norm, eps) @ kv_b, nope + d_v)
    k_rot = rope(k_rot[:, None], positions, theta)        # [b, 1, S, rot]
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rot, (b, h, s, rot))], -1)
    v = kv[..., nope:]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def some_heads(qkv):
        qh, kh, vh = qkv                                   # [b, g, S, *]
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) \
            / np.sqrt(nope + rot)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, vh)

    g = HEADS_AT_A_TIME if h % HEADS_AT_A_TIME == 0 else h

    def grouped(t):                       # [b, h, S, w] -> [h/g, b, g, S, w]
        return t.reshape(b, h // g, g, s, -1).transpose(1, 0, 2, 3, 4)

    out = jax.lax.map(jax.checkpoint(some_heads),
                      (grouped(q), grouped(k), grouped(v)))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, h, s, d_v)
    return out.transpose(0, 2, 1, 3).reshape(b, s, h * d_v) @ w_o


def gated_ffn(x, w_gate, w_up, w_down):
    import jax
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(x, w_router, bias, m):
    """(expert ids [.., k], weights [.., k]): the top-k of score + bias,
    weighted by the scores alone, normalised and scaled."""
    import jax
    import jax.numpy as jnp
    scores = jax.nn.sigmoid(x @ w_router)
    _, idx = jax.lax.top_k(scores + bias, int(m["num_experts_per_tok"]))
    picked = jnp.take_along_axis(scores, idx, -1)
    picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return idx, picked * float(m["routed_scaling_factor"])


def routed_experts(x, idx, weights, w_gate, w_up, w_down, held, offset):
    """The part experts [offset, offset + held) give: each of them run
    on every token, times the weight the routing gives it there (0
    where it was not picked). The stacked arrays hold the held experts
    by rows."""
    import jax.numpy as jnp
    d, f = x.shape[-1], w_gate.shape[-1]
    out = jnp.zeros_like(x)
    for e in range(held):
        gate = jnp.sum(jnp.where(idx == offset + e, weights, 0.0), -1)
        out = out + gate[..., None] * gated_ffn(
            x, w_gate[e * d:(e + 1) * d], w_up[e * d:(e + 1) * d],
            w_down[e * f:(e + 1) * f])
    return out


def moe_ffn(x, w, m):
    """w: router, bias, stacked gate/up/down of the held experts, the
    shared expert's gate/up/down."""
    held = int(m.get("experts_held") or m["n_routed_experts"])
    idx, weights = route(x, w[0], w[1], m)
    return routed_experts(x, idx, weights, w[2], w[3], w[4], held,
                          int(m.get("expert_offset", 0))) \
        + gated_ffn(x, *w[5:8])


def block(x, positions, w, m, dense):
    """One pre-norm block; w: attn norm, 7 attention arrays, ffn norm,
    then 3 (dense) or 8 (MoE) arrays."""
    eps = float(m["rms_norm_eps"])
    x = x + latent_attention(rms_norm(x, w[0], eps), positions, w[1:8], m)
    h = rms_norm(x, w[8], eps)
    return x + (gated_ffn(h, *w[9:12]) if dense else moe_ffn(h, w[9:17], m))


BLOCK_ARRAYS = {True: 12, False: 17}


class _Frozen(dict):
    """The builder arguments as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _token_loss_sum(hidden, head, labels):
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(hidden @ head, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


def loss_sum(tape, tokens, ahead1, ahead2, positions, m):
    """Summed over positions: CE(main, t+1) + mtp_loss_weight * CE(mtp,
    t+2). Integer [b, S] arrays."""
    import jax
    import jax.numpy as jnp
    it = iter(tape)

    def take(n):
        return [next(it) for _ in range(n)]

    eps = float(m["rms_norm_eps"])
    (table,) = take(1)
    x = table[tokens]
    for i in range(int(m["num_hidden_layers"])):
        dense = i < int(m["first_k_dense_replace"])
        x = jax.checkpoint(block, static_argnums=(3, 4))(
            x, positions, take(BLOCK_ARRAYS[dense]), _Frozen(m), dense)
    final_norm, head = take(2)
    hidden = rms_norm(x, final_norm, eps)
    total = _token_loss_sum(hidden, head, ahead1)
    if int(m["num_nextn_predict_layers"]):
        e_norm, h_norm, join = take(3)
        joined = jnp.concatenate([rms_norm(table[ahead1], e_norm, eps),
                                  rms_norm(hidden, h_norm, eps)], -1)
        x = jax.checkpoint(block, static_argnums=(3, 4))(
            joined @ join, positions, take(BLOCK_ARRAYS[False]),
            _Frozen(m), False)
        (mtp_norm,) = take(1)
        total = total + float(m["mtp_loss_weight"]) \
            * _token_loss_sum(rms_norm(x, mtp_norm, eps), head, ahead2)
    assert next(it, None) is None, \
        "the parameter tape is longer than the model"
    return total


def _batch(batch: dict):
    ids = [np.asarray(batch[k]).reshape(batch[k].shape[0], -1)
           .astype(np.int32) for k in ("trg_ids", "trg_labels", "src_ids")]
    return ids, np.arange(ids[0].shape[1], dtype=np.int32)


def _chunks(fn, tape_arrays, batch, model, chunk_tokens):
    """fn(tape, tokens, ahead1, ahead2, positions) over the batch a few
    whole sequences at a time (a causal model's reference does not cut
    a sequence), results in a list."""
    import jax
    import jax.numpy as jnp
    (tok, a1, a2), pos = _batch(batch)
    rows = max(1, chunk_tokens // tok.shape[1])
    tape = [jnp.asarray(a, jnp.float32) for a in tape_arrays]
    m = _Frozen(model)
    with jax.default_matmul_precision("highest"):
        jitted = jax.jit(lambda t, *ids: fn(t, *ids, m))
        return [jitted(tape, tok[i:i + rows], a1[i:i + rows],
                       a2[i:i + rows], pos)
                for i in range(0, tok.shape[0], rows)], tok.size


def loss(tape_arrays, batch: dict, model: dict,
         chunk_tokens: int = 4096) -> float:
    """The training loss on one feed batch ({"trg_ids", "trg_labels",
    "src_ids"}: [b, S, 1] ids = t_i, t_(i+1), t_(i+2)): both cross
    entropies are means over every position, nothing is masked.
    ``model`` is the configuration's builder arguments."""
    sums, count = _chunks(loss_sum, tape_arrays, batch, model,
                          chunk_tokens)
    return sum(float(s) for s in sums) / count


def grads(tape_arrays, batch: dict, model: dict,
          chunk_tokens: int = 4096) -> list:
    """Gradient of that loss with respect to every array of the tape
    (``jax.grad`` of the same plain forward). The selection bias only
    chooses, so its gradient is zero and it goes unscored. Device
    arrays, tape order."""
    import jax
    parts, count = _chunks(jax.grad(loss_sum), tape_arrays, batch, model,
                           chunk_tokens)
    total = parts[0]
    for g in parts[1:]:
        total = [a + b for a, b in zip(total, g)]
    return [g / count for g in total]
