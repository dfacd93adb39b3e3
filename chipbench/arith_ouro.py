"""Operations and bytes of the ``ouro-2p6b`` configuration's train step,
from shapes alone (conventions of chipbench/arith.py and
arith_laguna.py: a multiply-add is 2 FLOPs, backward is twice forward,
norms, rotary, softmax, activations, the exit distribution and the
optimizer are left out, a causal attention needs half the score
matrix). The stack of ``num_hidden_layers`` blocks is applied
``total_ut_steps`` times on the same weights: every application is
counted, whatever runs it (one loop in the program, a Python loop in
the reference), and so is the head once an exit. The keywords are the
builder's own (paddle_tpu/models/looped_lm.py), so a configuration's
builder arguments can be passed whole.
"""
from __future__ import annotations

from .arith_laguna import flash_call_cost, visible_pairs


def application_params(hidden_size, intermediate_size,
                       num_attention_heads, num_key_value_heads, head_dim,
                       **_unused) -> int:
    """Elements of the seven matrices one block application multiplies
    by: q, k, v, o and the FFN's gate, up, down."""
    d, w = hidden_size, head_dim
    return d * w * 2 * (num_attention_heads + num_key_value_heads) \
        + 3 * d * intermediate_size


def forward_flops(batch, seq, trg_vocab, hidden_size, intermediate_size,
                  num_hidden_layers, num_attention_heads,
                  num_key_value_heads, head_dim, total_ut_steps,
                  **_unused) -> dict:
    """Forward FLOPs of one step, by part."""
    tokens = batch * seq
    applications = total_ut_steps * num_hidden_layers
    parts = {
        "products": tokens * applications * 2 * application_params(
            hidden_size, intermediate_size, num_attention_heads,
            num_key_value_heads, head_dim),
        "attention": batch * applications * num_attention_heads
        * visible_pairs(seq) * 4 * head_dim,
        "heads": tokens * total_ut_steps * 2 * hidden_size * trg_vocab,
        "gate": tokens * total_ut_steps * 2 * hidden_size,
    }
    parts["total"] = sum(parts.values())
    return parts


def train_flops(batch, seq, **model) -> float:
    """Model FLOPs of one training step (forward + backward = 3 x
    forward), the numerator of an MFU."""
    return 3.0 * forward_flops(batch, seq, **model)["total"]


def flash_calls(num_hidden_layers, total_ut_steps, **_unused) -> int:
    """Flash calls a step EACH way: one a layer a pass (the program
    traces ``num_hidden_layers`` sites; the loop runs each
    ``total_ut_steps`` times)."""
    return num_hidden_layers * total_ut_steps


def flash_cost(batch, seq, num_hidden_layers, num_attention_heads,
               num_key_value_heads, head_dim, total_ut_steps,
               **_unused) -> dict:
    """Summed cost of every flash call of one training step, forward and
    backward: the same work whatever implements it."""
    calls = flash_calls(num_hidden_layers, total_ut_steps)
    out = {"flops": 0.0, "bytes": 0.0}
    for backward in (False, True):
        c = flash_call_cost(batch, num_attention_heads,
                            num_key_value_heads, seq, head_dim,
                            visible_pairs(seq), backward)
        for key in out:
            out[key] += calls * c[key]
    return out
