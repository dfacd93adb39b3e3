"""Operations and bytes of the ``laguna-xs2`` configuration's train step,
from shapes alone (conventions of chipbench/arith.py and arith_joyai.py:
a multiply-add is 2 FLOPs, backward is twice forward, norms, rotary,
softmax, activations, the head gates' product and the optimizer are left
out, a causal attention needs half the score matrix). A WINDOWED site is
booked at its band's own pairs — key j visible to query i iff
i - W < j <= i, so W (W + 1) / 2 + (S - W) W of them — and not at
S^2 / 2: a kernel that computes tiles the window masks does work that is
not counted here and reads a low share of its roofline. K and V are
counted at their own head count: no implementation has to read or write
them at the query heads'. The keywords are the builder's own
(paddle_tpu/models/decoder_moe.py), so a configuration's builder
arguments can be passed whole; the routed experts' rows are counted at
their expectation under uniform routing, as arith_joyai counts them.
"""
from __future__ import annotations


def visible_pairs(seq, window=None) -> float:
    """(query, key) pairs a causal site of one head computes: half the
    score matrix (arith.py's convention), or under a window its band,
    counted exactly."""
    if window is None or window >= seq:
        return 0.5 * seq * seq
    return window * (window + 1) / 2 + (seq - window) * window


def _sites(seq, num_hidden_layers, layer_types,
           num_attention_heads_per_layer, sliding_window):
    """(query heads, pairs, windowed) of each layer's attention site."""
    for kind, heads in list(zip(layer_types,
                                num_attention_heads_per_layer)
                            )[:num_hidden_layers]:
        windowed = kind == "sliding_attention"
        yield heads, visible_pairs(
            seq, sliding_window if windowed else None), windowed


def forward_flops(batch, seq, trg_vocab, hidden_size, intermediate_size,
                  moe_intermediate_size, shared_expert_intermediate_size,
                  n_routed_experts, experts_held, num_experts_per_tok,
                  num_hidden_layers, layer_types,
                  num_attention_heads_per_layer, mlp_layer_types,
                  num_key_value_heads, head_dim, sliding_window,
                  gating=True, **_unused) -> dict:
    """Forward FLOPs of one step, by part."""
    tokens = batch * seq
    d, w = hidden_size, head_dim
    proj = attn = 0.0
    for heads, pairs, _windowed in _sites(
            seq, num_hidden_layers, layer_types,
            num_attention_heads_per_layer, sliding_window):
        proj += tokens * 2 * d * (2 * heads * w + 2 * num_key_value_heads * w
                                  + (heads if gating else 0))
        attn += batch * heads * pairs * 2 * (w + w)
    kinds = list(mlp_layer_types)[:num_hidden_layers]
    dense, moe = kinds.count("dense"), kinds.count("sparse")
    held = experts_held or n_routed_experts
    parts = {
        "projections": proj,
        "attention": attn,
        "dense_ffn": tokens * dense * 6 * d * intermediate_size,
        "router": tokens * moe * 2 * d * n_routed_experts,
        "shared_experts": tokens * moe * 6 * d
        * shared_expert_intermediate_size,
        "routed_experts": tokens * moe * 6 * d * moe_intermediate_size
        * num_experts_per_tok * held / n_routed_experts,
        "heads": tokens * 2 * d * trg_vocab,
    }
    parts["total"] = sum(parts.values())
    return parts


def train_flops(batch, seq, **model) -> float:
    """Model FLOPs of one training step (forward + backward = 3 x
    forward), the numerator of an MFU."""
    return 3.0 * forward_flops(batch, seq, **model)["total"]


def flash_call_cost(batch, heads, kv_heads, seq, width, pairs,
                    backward, dtype_bytes=2) -> dict:
    """One flash call over ``pairs`` (query, key) pairs a head. Forward:
    QK^T and PV, 4 w a pair. Backward: the score recompute, dV, dP, dQ,
    dK, 10 w a pair. Bytes: every operand read or written once at its
    own head count — forward Q, O at the query heads, K, V at the key
    heads (+ the f32 row logsumexp); backward Q, O, dO in and dQ out at
    the query heads, K, V in and dK, dV out at the key heads."""
    flops = (10.0 if backward else 4.0) * batch * heads * pairs * width
    q = batch * heads * seq * width * dtype_bytes
    k = batch * kv_heads * seq * width * dtype_bytes
    lse = batch * heads * seq * 4
    nbytes = (4 * q + 4 * k + lse) if backward else (2 * q + 2 * k + lse)
    return {"flops": flops, "bytes": nbytes}


def flash_cost(batch, seq, num_hidden_layers, layer_types,
               num_attention_heads_per_layer, num_key_value_heads,
               head_dim, sliding_window, **_unused) -> dict:
    """Summed cost of every flash call of one training step, forward and
    backward (``flops``, ``bytes``), and of the windowed sites' calls
    alone (``window_flops``, ``window_bytes``): the same work whatever
    implements it."""
    out = dict(flops=0.0, bytes=0.0, window_flops=0.0, window_bytes=0.0)
    for heads, pairs, windowed in _sites(
            seq, num_hidden_layers, layer_types,
            num_attention_heads_per_layer, sliding_window):
        for backward in (False, True):
            c = flash_call_cost(batch, heads, num_key_value_heads, seq,
                                head_dim, pairs, backward)
            for key in ("flops", "bytes"):
                out[key] += c[key]
                if windowed:
                    out["window_" + key] += c[key]
    return out
