"""Operations and bytes of the ``joyai-llm-flash`` configuration's train
step, from shapes alone (conventions of chipbench/arith.py: a
multiply-add is 2 FLOPs, backward is twice forward, norms, rotary,
softmax, activations and the optimizer are left out, a causal attention
needs half the score matrix). The keywords are the builder's own
(paddle_tpu/models/decoder_moe.py), so a configuration's builder
arguments can be passed whole.

The routed experts' rows are run-time data. They are counted at their
EXPECTATION under uniform routing — tokens x experts a token x held /
total — which is also what a balanced deployment sends a chip; a step
whose routing sends the held experts more does more work than is
booked here, one that sends less does less.
"""
from __future__ import annotations


def forward_flops(batch, seq, trg_vocab, hidden_size, num_attention_heads,
                  q_lora_rank, kv_lora_rank, qk_nope_head_dim,
                  qk_rope_head_dim, v_head_dim, intermediate_size,
                  moe_intermediate_size, n_routed_experts, experts_held,
                  num_experts_per_tok, n_shared_experts, num_hidden_layers,
                  first_k_dense_replace, num_nextn_predict_layers,
                  **_unused) -> dict:
    """Forward FLOPs of one step, by part."""
    tokens = batch * seq
    d, h = hidden_size, num_attention_heads
    d_qk = qk_nope_head_dim + qk_rope_head_dim
    blocks = num_hidden_layers + num_nextn_predict_layers
    dense = min(first_k_dense_replace, num_hidden_layers)
    moe = blocks - dense
    proj = 2 * (d * q_lora_rank + q_lora_rank * h * d_qk
                + d * (kv_lora_rank + qk_rope_head_dim)
                + kv_lora_rank * h * (qk_nope_head_dim + v_head_dim)
                + h * v_head_dim * d)
    attn = seq * h * (d_qk + v_head_dim)       # causal: half of 2 S (..)
    expert = 6 * d * moe_intermediate_size     # gate, up, down
    held = experts_held or n_routed_experts
    parts = {
        "projections": tokens * blocks * proj,
        "attention": tokens * blocks * attn,
        "dense_ffn": tokens * dense * 6 * d * intermediate_size,
        "router": tokens * moe * 2 * d * n_routed_experts,
        "shared_experts": tokens * moe * n_shared_experts * expert,
        "routed_experts": tokens * moe * expert * num_experts_per_tok
        * held / n_routed_experts,
        "heads": tokens * (1 + num_nextn_predict_layers) * 2 * d
        * trg_vocab,
        "mtp_join": tokens * num_nextn_predict_layers * 2 * 2 * d * d,
    }
    parts["total"] = sum(parts.values())
    return parts


def train_flops(batch, seq, **model) -> float:
    """Model FLOPs of one training step (forward + backward = 3 x
    forward), the numerator of an MFU."""
    return 3.0 * forward_flops(batch, seq, **model)["total"]


def flash_call_cost_two_widths(batch, n_head, seq_q, seq_k, d_qk, d_v,
                               causal, backward, dtype_bytes=2) -> dict:
    """chipbench.arith.flash_call_cost for a value head of another width
    than the key head. Forward: QK^T (2 S_q S_k d_qk) and PV (2 S_q S_k
    d_v). Backward: the score recompute, dQ and dK at d_qk, dV and dP at
    d_v: 2 S_q S_k (3 d_qk + 2 d_v). Bytes: every operand read or
    written once at its own width — forward Q, K, V in, O out (+ the f32
    row logsumexp); backward Q, K, V, O, dO in, dQ, dK, dV out."""
    bh = batch * n_head
    pairs = seq_q * seq_k * (0.5 if causal else 1.0)
    flops = 2.0 * bh * pairs * ((3 * d_qk + 2 * d_v) if backward
                                else (d_qk + d_v))
    q = bh * seq_q * d_qk * dtype_bytes
    k = bh * seq_k * d_qk * dtype_bytes
    v = bh * seq_k * d_v * dtype_bytes
    o = bh * seq_q * d_v * dtype_bytes
    lse = bh * seq_q * 4
    nbytes = (2 * q + 2 * k + 2 * v + 2 * o + lse) if backward \
        else (q + k + v + o + lse)
    return {"flops": flops, "bytes": nbytes}


def flash_cost(batch, seq, num_attention_heads, qk_nope_head_dim,
               qk_rope_head_dim, v_head_dim, num_hidden_layers,
               num_nextn_predict_layers, **_unused) -> dict:
    """Summed cost of every flash call of one training step: one causal
    self-attention a block (the stack's and each prediction module's),
    forward and backward."""
    flops = nbytes = 0.0
    for backward in (False, True):
        c = flash_call_cost_two_widths(
            batch, num_attention_heads, seq, seq,
            qk_nope_head_dim + qk_rope_head_dim, v_head_dim, True,
            backward)
        flops += (num_hidden_layers + num_nextn_predict_layers) * c["flops"]
        nbytes += (num_hidden_layers + num_nextn_predict_layers) * c["bytes"]
    return {"flops": flops, "bytes": nbytes}
