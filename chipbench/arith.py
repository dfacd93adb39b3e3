"""Operations and bytes the algorithms need, from shapes alone. Kept
with the benchmark so that no PR that claims a gain can change the
yardstick; cross-checked once against analysis/cost_model.py and XLA's
cost_analysis in tests/chipbench/test_arith.py.

Conventions: a multiply-add is 2 FLOPs; backward is twice forward for
a matmul; softmax, layer norm, bias and activation FLOPs are left out
(under 1 % of a transformer step at these widths); a causal attention
needs half the score matrix; recomputation does not count towards a
model's FLOPs but IS part of the flash backward algorithm, which
exists to trade it for memory.
"""
from __future__ import annotations


def encdec_forward_flops(batch, seq, n_layer, d_model, d_inner,
                         trg_vocab, **_unused) -> dict:
    """Forward FLOPs of one step of the encoder-decoder transformer of
    models/transformer.py with source and target both ``seq`` long, by
    part; the keywords are ``build_train``'s own, so a configuration's
    builder arguments can be passed whole. Attention: encoder self (full), decoder self (causal: half),
    cross (full)."""
    pos = batch * seq                         # positions per side
    proj = 8 * d_model * d_model              # q,k,v,out: 4 x 2 d^2
    ffn = 4 * d_model * d_inner               # two matmuls
    full = 4 * seq * d_model                  # QK^T + PV per query row
    enc = n_layer * pos * (proj + ffn)
    dec = n_layer * pos * (2 * proj + ffn)
    attn = n_layer * pos * (full + full // 2 + full)
    head = pos * 2 * d_model * trg_vocab
    return {"matmul": enc + dec, "attention": attn, "head": head,
            "total": enc + dec + attn + head}


def encdec_train_flops(batch, seq, **model) -> float:
    """Model FLOPs of one training step (forward + backward = 3 x
    forward), the numerator of an MFU."""
    return 3.0 * encdec_forward_flops(batch, seq, **model)["total"]


def flash_call_cost(batch, n_head, seq_q, seq_k, d_head, causal,
                    backward, dtype_bytes=2) -> dict:
    """FLOPs and HBM bytes one flash-attention call needs. Forward:
    QK^T and PV (4 S_q S_k d per head). Backward: the score recompute,
    dV, dP, dQ, dK (10 S_q S_k d). Bytes: every operand read or written
    once — forward Q, K, V in and O out (+ the f32 row logsumexp);
    backward Q, K, V, O, dO in and dQ, dK, dV out."""
    bh = batch * n_head
    pairs = seq_q * seq_k * (0.5 if causal else 1.0)
    flops = (10.0 if backward else 4.0) * bh * pairs * d_head
    q_bytes = bh * seq_q * d_head * dtype_bytes
    k_bytes = bh * seq_k * d_head * dtype_bytes
    lse = bh * seq_q * 4
    if backward:
        nbytes = 3 * q_bytes + 2 * k_bytes + 2 * k_bytes + q_bytes + lse
    else:
        nbytes = 2 * q_bytes + 2 * k_bytes + lse
    return {"flops": flops, "bytes": nbytes}


def encdec_flash_cost(batch, seq, n_layer, n_head, d_model,
                      **_unused) -> dict:
    """Summed cost of every flash call of one training step: per layer
    an encoder self-attention, a decoder self-attention (causal) and a
    cross-attention, each forward and backward."""
    d_head = d_model // n_head
    flops = nbytes = 0.0
    for causal in (False, True, False):
        for backward in (False, True):
            c = flash_call_cost(batch, n_head, seq, seq, d_head, causal,
                                backward)
            flops += n_layer * c["flops"]
            nbytes += n_layer * c["bytes"]
    return {"flops": flops, "bytes": nbytes}


def roofline_seconds(flops, nbytes, peaks) -> dict:
    """The least time the chip could take, and which limit binds."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_f, t_b),
            "bound": "compute" if t_f >= t_b else "bandwidth"}
