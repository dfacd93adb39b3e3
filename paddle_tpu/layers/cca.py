"""Layer wrappers of the compressed-convolutional-attention and
MLP-router ops (ops/cca_ops.py). As with layers/ssm.py the parameters
and the persistable windows are the caller's: a served model creates
them once and hands them to the prefill and the decode program alike
(models/cca_moe.py)."""
from __future__ import annotations

from ..layer_helper import LayerHelper

__all__ = ["grouped_causal_conv1d", "grouped_conv_state_update",
           "cca_qk_mix", "mlp_router"]


def grouped_causal_conv1d(x, w, bias, length, heads):
    """x [n, S, heads * width], w [K * heads * width, width], bias
    [heads * width], length [n] -> (out [n, S, C], the last K - 1 real
    inputs [n, (K - 1) * C])."""
    helper = LayerHelper("grouped_causal_conv1d")
    out = helper.create_tmp_variable(x.dtype)
    state = helper.create_tmp_variable(x.dtype)
    helper.append_op(
        type="grouped_causal_conv1d",
        inputs={"X": x, "W": w, "Bias": bias, "Length": length},
        outputs={"Out": out, "State": state}, attrs={"heads": int(heads)})
    return out, state


def grouped_conv_state_update(state, x, w, bias, heads):
    """One token a slot; ``state`` (persistable) is updated in place."""
    helper = LayerHelper("grouped_conv_state_update")
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(
        type="grouped_conv_state_update",
        inputs={"State": state, "X": x, "W": w, "Bias": bias},
        outputs={"Out": out, "StateOut": state},
        attrs={"heads": int(heads)})
    return out


def cca_qk_mix(z, b, tau, heads, kv_heads):
    """The pre-convolution latent z and the convolutions' output b
    [.., (heads + kv_heads) * width], tau [kv_heads] -> (q [.., heads *
    width], k [.., kv_heads * width]): joined with the pre-convolution
    mean, each head L2-normalised."""
    helper = LayerHelper("cca_qk_mix")
    q = helper.create_tmp_variable(z.dtype)
    k = helper.create_tmp_variable(z.dtype)
    helper.append_op(type="cca_qk_mix",
                     inputs={"Z": z, "B": b, "Tau": tau},
                     outputs={"Q": q, "K": k},
                     attrs={"heads": int(heads), "kv_heads": int(kv_heads)})
    return q, k


def mlp_router(x, length, arrays, r_prev=None):
    """Top-1 routing of x [n, S, d] (ops/cca_ops.py mlp_router):
    ``arrays`` holds the op's parameter inputs by slot (WDown, Gamma,
    W1, B1, W2, B2, W3, SelectBias), ``r_prev`` the previous layer's r.
    Returns (ids [n, S, 1] int32, weights [n, S, 1], r [n, S, h], counts
    [experts + 1] int32)."""
    helper = LayerHelper("mlp_router")
    idx = helper.create_tmp_variable("int32")
    weights = helper.create_tmp_variable("float32")
    r = helper.create_tmp_variable("float32")
    counts = helper.create_tmp_variable("int32")
    for v in (idx, weights, r, counts):
        v.stop_gradient = True
    inputs = dict(arrays, X=x, Length=length)
    if r_prev is not None:
        inputs["RPrev"] = r_prev
    helper.append_op(type="mlp_router", inputs=inputs,
                     outputs={"TopIdx": idx, "TopW": weights, "R": r,
                              "Counts": counts}, attrs={})
    return idx, weights, r, counts
