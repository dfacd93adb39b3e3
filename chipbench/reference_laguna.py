"""Plain reference of the ``laguna-xs2`` configuration: the forward pass,
its loss and ``jax.grad`` of it in straightforward ``jax.numpy``, float32
under ``default_matmul_precision("highest")``: a masked softmax over the
whole score matrix (no kernel, no band arithmetic, K and V repeated to
the query heads by ``jnp.repeat``), no sort, no grouped product. Weights
are the system's own, read as a tape in the order
``paddle_tpu/models/decoder_moe.py`` creates them. Nothing is imported
from ``paddle_tpu``; the norm, the gated FFN and the held experts' part
are ``reference_joyai``'s own few lines.

It follows the published config
(https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json):
pre-norm blocks; grouped-query attention, 48 query heads on a
"full_attention" layer and 64 on a "sliding_attention" layer over 8 key
heads of 128; rotary embedding by the layer kind's ``rope_parameters``
block (theta 1e4 over all 128 columns under the window; YaRN at theta
5e5 over the first 64 columns on a full layer, cos and sin times the
attention factor); a causal mask, and under the window key j visible to
query i iff i - 512 < j <= i (``kv > q - sliding_window``, the Hugging
Face convention: 512 keys, the query's own among them); a gate a head on
the attention output; a dense gated FFN in layer 0, then a router over
256 experts (top 8), the held experts and one shared expert. The same
share of the deployment as the program: only experts [expert_offset,
expert_offset + experts_held) are computed, each for EVERY token and
masked by the routing, and the vocabulary is the slice. What the config
names without giving a form is set by the family's convention, each at
the line that makes it (``assumed`` in the configuration's file has the
reasoning). Attention goes a few heads and ``Q_ROWS`` query rows at a
time (``jax.lax.map``) and every block is a ``jax.checkpoint``, so that
8192 x 8192 scores of 64 heads fit beside the program's state; neither
changes a number.
"""
from __future__ import annotations

import math

import numpy as np

from .reference_joyai import (_Frozen, gated_ffn, rms_norm,
                              routed_experts)

HEADS_AT_A_TIME = 4
Q_ROWS = 2048


def inv_freq(params, head_dim):
    """(frequencies [r/2], the factor on cos and sin, r) of one
    ``rope_parameters`` block; YaRN as Hugging Face computes it."""
    r = int(head_dim * float(params.get("partial_rotary_factor", 1)))
    theta = float(params["rope_theta"])
    f = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    if params.get("rope_type", "default") != "yarn":
        return f, 1.0, r
    factor = float(params["factor"])
    original = float(params["original_max_position_embeddings"])

    def pair_turning(turns):
        return r * math.log(original / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    lo = max(math.floor(pair_turning(float(params["beta_fast"]))), 0)
    hi = min(math.ceil(pair_turning(float(params["beta_slow"]))), r - 1)
    keep = 1.0 - np.clip((np.arange(r // 2) - lo) / (hi - lo), 0.0, 1.0)
    return (f / factor * (1.0 - keep) + f * keep,
            float(params["attention_factor"]), r)


def rope(x, positions, params):
    """x [..., S, w]: columns (i, i + r/2) of the first r turned by
    position x frequency i; the rest untouched."""
    import jax.numpy as jnp
    f, factor, r = inv_freq(params, x.shape[-1])
    angle = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(f, jnp.float32)[None, :]
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    # assumed (e): rotate-half layout, the rotated columns first
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., r:]], -1)


def gqa_attention(x, positions, w, m, layer):
    """x [b, S, d] (normed); w: the block's q, k, v, gate and o."""
    import jax
    import jax.numpy as jnp
    w_q, w_k, w_v, w_g, w_o = w
    b, s, _ = x.shape
    h = int(m["num_attention_heads_per_layer"][layer])
    h_kv, d = int(m["num_key_value_heads"]), int(m["head_dim"])
    kind = m["layer_types"][layer]
    params = m["rope_parameters"][kind]
    window = int(m["sliding_window"]) if kind == "sliding_attention" \
        else None

    def heads(t, n):
        return t.reshape(b, s, n, d).transpose(0, 2, 1, 3)

    # assumed (d): no normalisation of q or k
    q = rope(heads(x @ w_q, h), positions, params)
    k = rope(heads(x @ w_k, h_kv), positions, params)
    v = heads(x @ w_v, h_kv)
    k, v = (jnp.repeat(t, h // h_kv, axis=1) for t in (k, v))
    g = HEADS_AT_A_TIME if h % HEADS_AT_A_TIME == 0 else h
    rows = Q_ROWS if s % Q_ROWS == 0 else s
    kpos = jnp.arange(s)[None, :]

    def some_rows(args):
        qh, kh, vh, first = args            # [b, g, rows, d], [b, g, S, d]
        qpos = first + jnp.arange(rows)[:, None]
        seen = kpos <= qpos
        if window is not None:
            seen = seen & (kpos > qpos - window)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / np.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, vh)

    def some_heads(args):
        qh, kh, vh = args                                  # [b, g, S, d]
        blocks = qh.reshape(b, g, s // rows, rows, d).transpose(
            2, 0, 1, 3, 4)
        out = jax.lax.map(
            jax.checkpoint(lambda a: some_rows((a[0], kh, vh, a[1]))),
            (blocks, jnp.arange(0, s, rows)))
        return out.transpose(1, 2, 0, 3, 4).reshape(b, g, s, d)

    def grouped(t):                       # [b, h, S, d] -> [h/g, b, g, S, d]
        return t.reshape(b, h // g, g, s, d).transpose(1, 0, 2, 3, 4)

    out = jax.lax.map(some_heads, (grouped(q), grouped(k), grouped(v)))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, h, s, d)
    if m.get("gating"):
        # assumed (a): the gate is head-wise, a sigmoid of the layer's
        # normed input through a [d_model, heads] matrix
        out = out * jax.nn.sigmoid(x @ w_g).transpose(0, 2, 1)[..., None]
    return out.transpose(0, 2, 1, 3).reshape(b, s, h * d) @ w_o


def route(x, w_router, m):
    """(expert ids [.., k], weights [.., k])."""
    import jax
    import jax.numpy as jnp
    # assumed (b): sigmoid scores, no selection bias, the picked scores
    # normalised and times the routed scaling factor
    scores = jax.nn.sigmoid(x @ w_router)
    picked, idx = jax.lax.top_k(scores, int(m["num_experts_per_tok"]))
    picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return idx, picked * float(m["routed_scaling_factor"])


def moe_ffn(x, w, m):
    """w: router, stacked gate/up/down of the held experts, the shared
    expert's gate/up/down."""
    held = int(m.get("experts_held") or m["n_routed_experts"])
    idx, weights = route(x, w[0], m)
    # assumed (c): SiLU-gated FFNs, the weights on the experts' OUTPUT
    return routed_experts(x, idx, weights, w[1], w[2], w[3], held,
                          int(m.get("expert_offset", 0))) \
        + gated_ffn(x, *w[4:7])


BLOCK_ARRAYS = {True: 10, False: 14}


def block(x, positions, w, m, layer, dense):
    """One pre-norm block (assumed (f)); w: attn norm, q, k, v, gate, o,
    ffn norm, then 3 (dense) or 7 (MoE) arrays."""
    eps = float(m["rms_norm_eps"])
    x = x + gqa_attention(rms_norm(x, w[0], eps), positions, w[1:6], m,
                          layer)
    h = rms_norm(x, w[6], eps)
    return x + (gated_ffn(h, *w[7:10]) if dense else moe_ffn(h, w[7:14], m))


def loss_sum(tape, tokens, labels, positions, m):
    """Cross entropy of the next token, summed over positions. Integer
    [b, S] arrays."""
    import jax
    import jax.numpy as jnp
    it = iter(tape)

    def take(n):
        return [next(it) for _ in range(n)]

    (table,) = take(1)
    x = table[tokens]
    for i in range(int(m["num_hidden_layers"])):
        dense = m["mlp_layer_types"][i] == "dense"
        x = jax.checkpoint(block, static_argnums=(3, 4, 5))(
            x, positions, take(BLOCK_ARRAYS[dense]), _Frozen(m), i, dense)
    final_norm, head = take(2)
    assert next(it, None) is None, \
        "the parameter tape is longer than the model"
    logp = jax.nn.log_softmax(
        rms_norm(x, final_norm, float(m["rms_norm_eps"])) @ head, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


def _chunks(fn, tape_arrays, batch, model, chunk_tokens):
    """fn(tape, tokens, labels, positions) over the batch a few whole
    sequences at a time, results in a list."""
    import jax
    import jax.numpy as jnp
    tok, lab = (np.asarray(batch[k]).reshape(batch[k].shape[0], -1)
                .astype(np.int32) for k in ("trg_ids", "trg_labels"))
    pos = np.arange(tok.shape[1], dtype=np.int32)
    rows = max(1, chunk_tokens // tok.shape[1])
    tape = [jnp.asarray(a, jnp.float32) for a in tape_arrays]
    m = _Frozen(model)
    with jax.default_matmul_precision("highest"):
        jitted = jax.jit(lambda t, *ids: fn(t, *ids, m))
        return [jitted(tape, tok[i:i + rows], lab[i:i + rows], pos)
                for i in range(0, tok.shape[0], rows)], tok.size


def loss(tape_arrays, batch: dict, model: dict,
         chunk_tokens: int = 8192) -> float:
    """The training loss on one feed batch ({"trg_ids", "trg_labels"}:
    [b, S, 1] ids = t_i, t_(i+1); "src_ids" is fed and unused): the mean
    over every position, nothing is masked. ``model`` is the
    configuration's builder arguments."""
    sums, count = _chunks(loss_sum, tape_arrays, batch, model,
                          chunk_tokens)
    return sum(float(s) for s in sums) / count


def grads(tape_arrays, batch: dict, model: dict,
          chunk_tokens: int = 8192) -> list:
    """Gradient of that loss with respect to every array of the tape
    (``jax.grad`` of the same plain forward). Device arrays, tape
    order."""
    import jax
    parts, count = _chunks(jax.grad(loss_sum), tape_arrays, batch, model,
                           chunk_tokens)
    total = parts[0]
    for g in parts[1:]:
        total = [a + b for a, b in zip(total, g)]
    return [g / count for g in total]
