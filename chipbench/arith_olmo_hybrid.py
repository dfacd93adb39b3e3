"""Parameters, bytes and operations of the ``olmo-hybrid-7b``
configuration on the token server, from shapes alone (conventions of
chipbench/arith.py: a multiply-add is 2 FLOPs; norms, activations, the
softmax and the gating are left out). The keywords are the published
config keys, so a configuration's file can be passed whole (``**cfg``).

At the published sizes: a linear-attention layer holds 215.57 M
parameters, a full-attention layer 185.80 M, a period of four 832.5 M;
the embedding and the head 385.35 M each. 16 layers and the two tables
are 4,100.7 M = 8.20 GB in bfloat16; 40 slots reserve 1.06 GB of matrix
state (float32), 0.03 GB of convolution windows and 5.03 GB of K and V
to 2048 positions (tests/chipbench/test_chipbench_olmo_hybrid.py pins
them).
"""
from __future__ import annotations

from .arith import roofline_seconds

WIDTH = {"bfloat16": 2, "float32": 4}


def _dims(hidden_size, num_attention_heads, num_key_value_heads,
          linear_num_value_heads, linear_key_head_dim,
          linear_value_head_dim, linear_conv_kernel_dim, **_unused) -> dict:
    heads = linear_num_value_heads
    return dict(heads=heads, key=heads * linear_key_head_dim,
                value=heads * linear_value_head_dim,
                d_k=linear_key_head_dim, d_v=linear_value_head_dim,
                taps=linear_conv_kernel_dim,
                head_dim=hidden_size // num_attention_heads,
                n_head=num_attention_heads, n_kv=num_key_value_heads)


def _linear_layers(cfg) -> int:
    return sum(1 for k in cfg["layer_types"] if k == "linear_attention")


def layer_matrix_params(kind, **arch) -> int:
    """Elements of the matrices one token is multiplied by in a layer:
    the mixer's projections and the FFN's gate, up and down."""
    d, m = arch["hidden_size"], _dims(**arch)
    ffn = 3 * d * arch["intermediate_size"]
    if kind == "linear_attention":
        # q, k, v, the gate z, a and b, the output
        return d * (2 * m["key"] + 2 * m["value"] + 2 * m["heads"]) \
            + m["value"] * d + ffn
    return d * m["head_dim"] * 2 * (m["n_head"] + m["n_kv"]) + ffn


def layer_small_params(kind, **arch) -> dict:
    """{"weights": elements stored at the weights' width, "scales":
    elements stored float32} beside a layer's matrices: the three
    convolutions' taps; the block's two norms, and a linear layer's
    A_log, dt_bias and output norm or a full layer's q and k norms."""
    d, m = arch["hidden_size"], _dims(**arch)
    if kind == "linear_attention":
        return {"weights": m["taps"] * (2 * m["key"] + m["value"]),
                "scales": 2 * d + 2 * m["heads"] + m["d_v"]}
    return {"weights": 0,
            "scales": 2 * d + (m["n_head"] + m["n_kv"]) * m["head_dim"]}


def layer_params(kind, **arch) -> int:
    small = layer_small_params(kind, **arch)
    return layer_matrix_params(kind, **arch) + small["weights"] \
        + small["scales"]


def model_params(**cfg) -> int:
    """Every parameter: the layers, the embedding, the last norm and
    the head (not tied)."""
    return sum(layer_params(k, **cfg) for k in cfg["layer_types"]) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def weight_bytes(storage_dtypes, **cfg) -> int:
    """Bytes of the stored parameters: matrices, both tables and the
    taps at ``weights``; norm scales, A_log and dt_bias at ``scales``."""
    wide = 2 * cfg["vocab_size"] * cfg["hidden_size"]
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
    n_linear = _linear_layers(cfg)
    n_full = len(cfg["layer_types"]) - n_linear
    return {
        "delta": slots * n_linear * m["d_k"] * m["value"]
        * WIDTH[storage_dtypes["delta"]],
        "conv": slots * n_linear * (m["taps"] - 1)
        * (2 * m["key"] + m["value"]) * WIDTH[storage_dtypes["conv"]],
        "kv": slots * n_full * 2 * m["n_kv"] * m["head_dim"] * max_seq_len
        * WIDTH[storage_dtypes["kv"]]}


def decode_step_bytes(slots, live_positions, storage_dtypes, **cfg) -> dict:
    """Bytes one decode step must move, by kind: every weight once (of
    the embedding only the slots' rows), the matrix state and the
    convolution windows read AND written, and the live keys and values
    (``live_positions``: the slots' contexts summed)."""
    m = _dims(**cfg)
    reserved = state_bytes(slots, 1, storage_dtypes, **cfg)
    n_full = len(cfg["layer_types"]) - _linear_layers(cfg)
    table = cfg["vocab_size"] * cfg["hidden_size"] \
        * WIDTH[storage_dtypes["weights"]]
    out = {"weights": weight_bytes(storage_dtypes, **cfg) - table,
           "delta": 2 * reserved["delta"], "conv": 2 * reserved["conv"],
           "kv_live": live_positions * n_full * 2 * m["n_kv"]
           * m["head_dim"] * WIDTH[storage_dtypes["kv"]]}
    out["total"] = sum(out.values())
    return out


def delta_update_cost(slots, **cfg) -> dict:
    """FLOPs and bytes of ONE layer's ``delta_state_update`` call: the
    state read and written (float32); the four rows over the value
    columns in (alpha, beta, v, k . q) and one out (o); every head's key
    and query. Seven operations an element of the state (the decay's
    product, two reads' product and add, the outer product's product
    and add)."""
    m = _dims(**cfg)
    state = slots * m["d_k"] * m["value"]
    return {"flops": 7 * state,
            "bytes": 4 * (2 * state + 5 * slots * m["value"]
                          + 2 * slots * m["key"])}


def delta_update_seconds(slots, peaks, **cfg) -> dict:
    """The least time the chip could take for one such call (bytes
    bind: 0.9 FLOPs a byte)."""
    cost = delta_update_cost(slots, **cfg)
    return roofline_seconds(cost["flops"], cost["bytes"], peaks)


def _recurrence_flops(**cfg) -> int:
    """One token through one linear layer's recurrence and its three
    convolutions, as written: seven operations an element of the state,
    two a tap."""
    m = _dims(**cfg)
    return 7 * m["d_k"] * m["value"] \
        + 2 * m["taps"] * (2 * m["key"] + m["value"])


def _stack_flops_a_token(**cfg) -> int:
    """Matrix products and recurrences of one token through every
    layer."""
    return sum(2 * layer_matrix_params(k, **cfg)
               + (_recurrence_flops(**cfg) if k == "linear_attention"
                  else 0) for k in cfg["layer_types"])


def _attention_flops(pairs, **cfg) -> int:
    """Scores and context over ``pairs`` (query, key) pairs a head, in
    every full-attention layer."""
    m = _dims(**cfg)
    n_full = len(cfg["layer_types"]) - _linear_layers(cfg)
    return n_full * m["n_head"] * 4 * m["head_dim"] * pairs


def head_flops(**cfg) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill_flops(tokens, **cfg) -> int:
    """Model FLOPs of a prompt of ``tokens``: every token through the
    stack (the recurrence counted as written, not as the chunked form
    computes it), the causal half of the score square, the head ONCE."""
    return tokens * _stack_flops_a_token(**cfg) \
        + _attention_flops(tokens * (tokens + 1) // 2, **cfg) \
        + head_flops(**cfg)


def decode_token_flops(context, **cfg) -> int:
    """Model FLOPs of one generated token whose attention reads
    ``context`` keys."""
    return _stack_flops_a_token(**cfg) + _attention_flops(context, **cfg) \
        + head_flops(**cfg)
