"""Parameters, bytes and operations of the ``zaya1-8b`` configuration on
the token server, from shapes alone (conventions of chipbench/arith.py:
a multiply-add is 2 FLOPs; norms, activations, the softmax, the mean
join and the head norms are left out). The keywords are the published
config keys, so a configuration's file can be passed whole (``**cfg``).

At the published sizes (tests/chipbench/test_chipbench_zaya.py pins
them): a layer holds 207.6 M parameters — attention 5.58 M (W_q 2048 x
1024, W_k 2048 x 256, W_v1 and W_v2 2048 x 128, W_o 1024 x 2048, the
grouped convolution 2 x 10 x 128 x 128), the router 0.66 M, the 16
experts 201.3 M — and the tied table 537.1 M. The 20 layers served are
4.689 B parameters = 9.38 GB in bfloat16; 96 slots reserve 4.03 GB of
keys and values to 2048 positions and 10 MB of windows.
"""
from __future__ import annotations

from .arith import roofline_seconds

WIDTH = {"bfloat16": 2, "float32": 4}


def _dims(hidden_size, head_dim, num_attention_heads, num_key_value_heads,
          cca_time0, cca_time1, num_experts, moe_intermediate_size,
          router_hidden_size, **_unused) -> dict:
    return dict(
        d=hidden_size, width=head_dim, n_head=num_attention_heads,
        n_kv=num_key_value_heads, q=num_attention_heads * head_dim,
        kv=num_key_value_heads * head_dim,
        latent=(num_attention_heads + num_key_value_heads) * head_dim,
        shifted=num_key_value_heads // 2 * head_dim, t0=cca_time0,
        t1=cca_time1, experts=num_experts, f=moe_intermediate_size,
        h=router_hidden_size)


def attention_matrix_params(**cfg) -> int:
    """Elements of the matrices a token is multiplied by in the
    attention sublayer: W_q, W_k, W_v1, W_v2, W_o and the grouped
    convolution's ``[d_h, d_h]`` matrix a head a tap."""
    m = _dims(**cfg)
    return m["d"] * (m["q"] + m["kv"] + 2 * m["shifted"]) + m["q"] * m["d"] \
        + m["t1"] * m["latent"] * m["width"]


def router_params(first=False, **cfg) -> int:
    """The router's float32 arrays: W_d, W_1, b_1, W_2, b_2, W_3, the
    balancing bias and (past the first layer) gamma."""
    m = _dims(**cfg)
    return m["d"] * m["h"] + 2 * (m["h"] * m["h"] + m["h"]) \
        + m["h"] * m["experts"] + m["experts"] + (0 if first else 1)


def routers_params(**cfg) -> int:
    """Every layer's router together (the first has no gamma)."""
    return len(cfg["layer_types"]) * router_params(**cfg) - 1


def expert_params(**cfg) -> int:
    """ONE expert: gate, up and down."""
    m = _dims(**cfg)
    return 3 * m["d"] * m["f"]


def layer_small_params(**cfg) -> dict:
    """{"weights": elements at the weights' width — the depthwise taps
    and both convolutions' biases —, "scales": float32 elements beside
    the router's: two norm scales and tau}."""
    m = _dims(**cfg)
    return {"weights": (m["t0"] + 2) * m["latent"],
            "scales": 2 * m["d"] + m["n_kv"]}


def layer_params(first=False, **cfg) -> int:
    small = layer_small_params(**cfg)
    return attention_matrix_params(**cfg) + router_params(first, **cfg) \
        + _dims(**cfg)["experts"] * expert_params(**cfg) \
        + small["weights"] + small["scales"]


def model_params(**cfg) -> int:
    """Every parameter: the layers, the tied embedding, the last norm."""
    n = len(cfg["layer_types"])
    return layer_params(True, **cfg) + (n - 1) * layer_params(**cfg) \
        + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def weight_bytes(storage_dtypes, **cfg) -> int:
    """Bytes of the stored parameters: matrices, experts, embedding,
    taps and biases at ``weights``; the router, norm scales and tau at
    ``scales``."""
    n = len(cfg["layer_types"])
    small = layer_small_params(**cfg)
    wide = cfg["vocab_size"] * cfg["hidden_size"] + n * (
        attention_matrix_params(**cfg) + small["weights"]
        + _dims(**cfg)["experts"] * expert_params(**cfg))
    narrow = cfg["hidden_size"] + n * small["scales"] \
        + routers_params(**cfg)
    return wide * WIDTH[storage_dtypes["weights"]] \
        + narrow * WIDTH[storage_dtypes["scales"]]


def state_bytes(slots, max_seq_len, storage_dtypes, **cfg) -> dict:
    """Bytes reserved for the slots' state, by kind: K and V at the key
    heads' columns a position, and the three windows."""
    m, n = _dims(**cfg), len(cfg["layer_types"])
    return {
        "kv": slots * n * 2 * m["kv"] * max_seq_len
        * WIDTH[storage_dtypes["kv"]],
        "conv": slots * n * ((m["t0"] - 1 + m["t1"] - 1) * m["latent"]
                             + m["shifted"])
        * WIDTH[storage_dtypes["conv"]]}


def decode_step_bytes(experts_read, live_positions, storage_dtypes,
                      **cfg) -> dict:
    """Bytes one decode step must move, by kind: the weights of the
    experts its picks HIT (``experts_read``: summed over the layers —
    an expert no row is sent to is not read), the attention sublayers'
    matrices, the routers, the head (the tied table, read once), and
    the live keys and values (``live_positions``: the slots' contexts
    summed)."""
    m, n = _dims(**cfg), len(cfg["layer_types"])
    wide = WIDTH[storage_dtypes["weights"]]
    out = {
        "experts": experts_read * expert_params(**cfg) * wide,
        "attention": n * attention_matrix_params(**cfg) * wide,
        "router": routers_params(**cfg) * WIDTH[storage_dtypes["scales"]],
        "head": cfg["vocab_size"] * cfg["hidden_size"] * wide,
        "kv_live": live_positions * n * 2 * m["kv"]
        * WIDTH[storage_dtypes["kv"]]}
    out["total"] = sum(out.values())
    return out


def experts_seconds(experts_read, rows, peaks, storage_dtypes,
                    **cfg) -> dict:
    """The least time the chip could take for the grouped products of
    decode steps that read ``experts_read`` experts (summed over layers
    and steps) for ``rows`` routed rows (likewise): each read expert's
    three matrices once, each row in and out of each product; 6 d f
    operations a row. At 6 rows an expert bytes bind."""
    m = _dims(**cfg)
    wide = WIDTH[storage_dtypes["weights"]]
    flops = 6 * rows * m["d"] * m["f"]
    moved = experts_read * expert_params(**cfg) * wide \
        + rows * (3 * m["d"] + 3 * m["f"]) * wide
    return roofline_seconds(flops, moved, peaks)


def decode_attention_seconds(slots, positions_read, peaks, storage_dtypes,
                             **cfg) -> dict:
    """The least time for ONE layer's cached attention of a decode step
    that reads ``positions_read`` cache positions (the slots' contexts
    summed for a read of the live rows; slots x bucket for a read to
    the bucket): K and V rows in, a query and a context row a head a
    slot; 4 d_h operations a query head a position."""
    m = _dims(**cfg)
    flops = 4 * m["n_head"] * m["width"] * positions_read
    moved = positions_read * 2 * m["kv"] * WIDTH[storage_dtypes["kv"]] \
        + slots * 2 * m["q"] * WIDTH[storage_dtypes["weights"]]
    return roofline_seconds(flops, moved, peaks)


def _stack_flops_a_token(**cfg) -> int:
    """Matrix products of one token through every layer: the attention
    sublayer's matrices (the depthwise taps among them), the router and
    ONE expert."""
    m, n = _dims(**cfg), len(cfg["layer_types"])
    router = m["d"] * m["h"] + 2 * m["h"] * m["h"] + m["h"] * m["experts"]
    return n * 2 * (attention_matrix_params(**cfg) + m["t0"] * m["latent"]
                    + router + expert_params(**cfg))


def _attention_flops(pairs, **cfg) -> int:
    """Scores and context over ``pairs`` (query, key) pairs a head, in
    every layer."""
    m = _dims(**cfg)
    return len(cfg["layer_types"]) * m["n_head"] * 4 * m["width"] * pairs


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
