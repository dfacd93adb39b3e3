"""Parameters, bytes and operations of the ``granite-4p0-h-micro``
configuration on the token server, from shapes alone (conventions of
chipbench/arith.py: a multiply-add is 2 FLOPs; norms, activations, the
softmax and the gating are left out). The keywords are the published
config keys, so a configuration's file can be passed whole (``**cfg``).

At the published sizes: a Mamba-2 layer holds 76.18 M parameters, an
attention layer 60.82 M, the model 3,191 M = 6.38 GB in bfloat16; 64
slots reserve 4.83 GB of recurrent state (float32), 0.06 GB of
convolution windows and 2.15 GB of KV cache to 4096 positions
(tests/chipbench/test_chipbench_granite.py pins them).
"""
from __future__ import annotations

from .arith import roofline_seconds

WIDTH = {"bfloat16": 2, "float32": 4}


def _dims(hidden_size, mamba_n_heads, mamba_d_head, mamba_d_state,
          mamba_d_conv, num_attention_heads, num_key_value_heads,
          **_unused) -> dict:
    inner = mamba_n_heads * mamba_d_head
    return dict(inner=inner, conv_dim=inner + 2 * mamba_d_state,
                in_width=2 * inner + 2 * mamba_d_state + mamba_n_heads,
                head_dim=hidden_size // num_attention_heads,
                taps=mamba_d_conv, heads=mamba_n_heads,
                n_kv=num_key_value_heads, n_head=num_attention_heads)


def layer_matrix_params(kind, **arch) -> int:
    """Elements of the matrices one token is multiplied by in a layer:
    the mixer's projections and the FFN's gate, up and down."""
    d, m = arch["hidden_size"], _dims(**arch)
    ffn = 3 * d * arch["intermediate_size"]
    if kind == "mamba":
        return d * m["in_width"] + m["inner"] * d + ffn
    return d * m["head_dim"] * 2 * (m["n_head"] + m["n_kv"]) + ffn


def layer_small_params(kind, **arch) -> dict:
    """{"weights": elements stored at the weights' width, "scales":
    elements stored float32} beside a layer's matrices."""
    d, m = arch["hidden_size"], _dims(**arch)
    if kind == "mamba":
        return {"weights": (m["taps"] + 1) * m["conv_dim"],
                "scales": 2 * d + m["inner"] + 3 * m["heads"]}
    return {"weights": 0, "scales": 2 * d}


def layer_params(kind, **arch) -> int:
    small = layer_small_params(kind, **arch)
    return layer_matrix_params(kind, **arch) + small["weights"] \
        + small["scales"]


def model_params(**cfg) -> int:
    """Every parameter: the layers, the tied embedding, the last norm."""
    return sum(layer_params(k, **cfg) for k in cfg["layer_types"]) \
        + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def weight_bytes(storage_dtypes, **cfg) -> int:
    """Bytes of the stored parameters: matrices, embedding, taps and
    bias at ``weights``, norm scales, A_log, dt_bias and D at
    ``scales``."""
    wide = cfg["vocab_size"] * cfg["hidden_size"]
    narrow = cfg["hidden_size"]
    for k in cfg["layer_types"]:
        small = layer_small_params(k, **cfg)
        wide += layer_matrix_params(k, **cfg) + small["weights"]
        narrow += small["scales"]
    return wide * WIDTH[storage_dtypes["weights"]] \
        + narrow * WIDTH[storage_dtypes["scales"]]


def state_bytes(slots, max_seq_len, storage_dtypes, **cfg) -> dict:
    """Bytes reserved for the slots' state, by kind."""
    m = _dims(**cfg)
    n_mamba = sum(1 for k in cfg["layer_types"] if k == "mamba")
    n_attn = len(cfg["layer_types"]) - n_mamba
    return {
        "ssm": slots * n_mamba * m["inner"] * cfg["mamba_d_state"]
        * WIDTH[storage_dtypes["ssm"]],
        "conv": slots * n_mamba * (m["taps"] - 1) * m["conv_dim"]
        * WIDTH[storage_dtypes["conv"]],
        "kv": slots * n_attn * 2 * m["n_kv"] * m["head_dim"] * max_seq_len
        * WIDTH[storage_dtypes["kv"]]}


def decode_step_bytes(slots, live_positions, storage_dtypes, **cfg) -> dict:
    """Bytes one decode step must move, by kind: every weight once, the
    recurrent state and the convolution windows read AND written, and
    the live keys and values (``live_positions``: the slots' contexts
    summed)."""
    m = _dims(**cfg)
    reserved = state_bytes(slots, 1, storage_dtypes, **cfg)
    n_attn = sum(1 for k in cfg["layer_types"] if k == "attention")
    out = {"weights": weight_bytes(storage_dtypes, **cfg),
           "ssm": 2 * reserved["ssm"], "conv": 2 * reserved["conv"],
           "kv_live": live_positions * n_attn * 2 * m["n_kv"]
           * m["head_dim"] * WIDTH[storage_dtypes["kv"]]}
    out["total"] = sum(out.values())
    return out


def ssm_update_cost(slots, **cfg) -> dict:
    """FLOPs and bytes of ONE layer's ``ssm_state_update`` call: the
    state read and written (float32), the rows of decay, dt x and y
    (float32) and the slots' B and C; five operations an element of
    the state (the decay's product, the outer product and its add, the
    contraction's product and add)."""
    m = _dims(**cfg)
    state = slots * m["inner"] * cfg["mamba_d_state"]
    return {"flops": 5 * state,
            "bytes": 4 * (2 * state + 3 * slots * m["inner"]
                          + 2 * slots * cfg["mamba_d_state"])}


def ssm_update_seconds(slots, peaks, **cfg) -> dict:
    """The least time the chip could take for one such call (bytes
    bind: 0.6 FLOPs a byte)."""
    cost = ssm_update_cost(slots, **cfg)
    return roofline_seconds(cost["flops"], cost["bytes"], peaks)


def _scan_flops(**cfg) -> int:
    """One token through one Mamba layer's recurrence and convolution,
    as written: five operations an element of the state, two a tap."""
    m = _dims(**cfg)
    return 5 * m["inner"] * cfg["mamba_d_state"] \
        + 2 * m["taps"] * m["conv_dim"]


def _stack_flops_a_token(**cfg) -> int:
    """Matrix products and scans of one token through every layer."""
    return sum(2 * layer_matrix_params(k, **cfg)
               + (_scan_flops(**cfg) if k == "mamba" else 0)
               for k in cfg["layer_types"])


def _attention_flops(pairs, **cfg) -> int:
    """Scores and context over ``pairs`` (query, key) pairs a head, in
    every attention layer."""
    m = _dims(**cfg)
    n_attn = sum(1 for k in cfg["layer_types"] if k == "attention")
    return n_attn * m["n_head"] * 4 * m["head_dim"] * pairs


def head_flops(**cfg) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill_flops(tokens, **cfg) -> int:
    """Model FLOPs of a prompt of ``tokens``: every token through the
    stack, the causal half of the score square, the head ONCE (only
    the last position's logits are needed)."""
    return tokens * _stack_flops_a_token(**cfg) \
        + _attention_flops(tokens * (tokens + 1) // 2, **cfg) \
        + head_flops(**cfg)


def decode_token_flops(context, **cfg) -> int:
    """Model FLOPs of one generated token whose attention reads
    ``context`` keys."""
    return _stack_flops_a_token(**cfg) + _attention_flops(context, **cfg) \
        + head_flops(**cfg)
