"""Neural-net layer functions — the primary user API.

Reference parity: python/paddle/fluid/layers/nn.py (fc:83, embedding:218,
dynamic_lstm:277, conv2d:1150, pool2d, batch_norm:1508, layer_norm:1597,
dropout, cross_entropy, softmax_with_cross_entropy:3165, sequence_*,
topk, accuracy, beam_search, matmul, nce:2836...). Each function builds
IR ops; XLA does the fusing.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Union

from ..framework import Variable
from ..layer_helper import LayerHelper, ParamAttr
from ..initializer import ConstantInitializer, NormalInitializer, \
    UniformInitializer, XavierInitializer

__all__ = [
    "fc", "fc_fanout", "embedding", "dynamic_lstm", "dynamic_gru", "conv2d",
    "depthwise_conv2d", "conv2d_transpose", "pool2d", "batch_norm",
    "layer_norm", "rms_norm", "rotary_embedding", "moe_router",
    "moe_experts", "dropout", "cross_entropy", "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits", "square_error_cost", "accuracy",
    "topk", "sequence_pool", "sequence_conv", "sequence_softmax",
    "sequence_expand", "sequence_first_step", "sequence_last_step",
    "sequence_reshape", "sequence_mask", "sequence_pad", "sequence_unpad",
    "sequence_reverse",
    "nested_sequence_flatten", "nested_sequence_pack",
    "im2sequence", "matmul", "mul", "softmax", "log_softmax", "relu", "lrn",
    "l2_normalize", "mean", "reduce_sum", "reduce_mean", "reduce_max",
    "reduce_min", "reduce_prod", "warpctc", "nce", "smooth_l1", "one_hot_v2",
    "clip", "clip_by_norm", "elementwise_add", "elementwise_sub",
    "elementwise_mul", "elementwise_div", "elementwise_max",
    "elementwise_min", "elementwise_pow", "scale", "cos_sim", "dot",
    "row_conv", "maxout", "scaled_dot_product_attention", "hsigmoid",
    "auc", "huber_loss", "log_loss", "kldiv_loss", "margin_rank_loss",
    "hinge_loss", "edit_distance", "pad2d", "leaky_relu", "elu", "pow",
    "swish", "hard_sigmoid", "relu6", "soft_relu", "flatten", "gelu",
    "beam_search", "beam_search_decode", "increment", "cumsum",
    "linear_chain_crf", "crf_decoding",
    "multiplex", "lstm_unit", "gru_unit", "dynamic_lstmp",
    "ctc_greedy_decoder", "chunk_eval", "autoincreased_step_counter",
    "lod_reset", "prelu", "label_smooth", "rank_loss", "roi_pool",
    "bilinear_interp", "nearest_interp", "resize_bilinear", "upsample",
    "sampling_id", "random_crop", "random_flip", "image_normalize",
    "augment_image",
]


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, is_test=False, name=None, dtype=None):
    """Fully-connected layer (reference: layers/nn.py:83). Multiple inputs
    are projected separately and summed, as in the reference."""
    helper = LayerHelper("fc", param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    dtype = dtype or inputs[0].dtype
    mul_results = []
    for inp in inputs:
        in_shape = inp.shape
        flat_dim = 1
        for d in in_shape[num_flatten_dims:]:
            flat_dim *= int(d)
        w = helper.create_parameter(helper.param_attr,
                                    shape=[flat_dim, size], dtype=dtype)
        out_shape = list(in_shape[:num_flatten_dims]) + [size]
        tmp = helper.create_tmp_variable(dtype, lod_level=inp.lod_level,
                                         shape=out_shape)
        helper.append_op(type="mul", inputs={"X": inp, "Y": w},
                         outputs={"Out": tmp},
                         attrs={"x_num_col_dims": num_flatten_dims
                                if inp.lod_level == 0 else inp.lod_level + 1,
                                "y_num_col_dims": 1})
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_tmp_variable(dtype)
        helper.append_op(type="sum", inputs={"X": mul_results},
                         outputs={"Out": pre_bias})
    pre_act = helper.append_bias_op(pre_bias, size=size)
    return helper.append_activation(pre_act)


def fc_fanout(input, sizes, num_flatten_dims=1, name=None):
    """`len(sizes)` bias-free fc projections of ONE dense input, as one
    `fanout_mul` op: the parameters `fc` would create, one a size, in
    that order and under those names, so a program that projects one
    activation several ways (q, k, v) holds what the separate `fc`
    calls gave it. One op lets the rule see the products together:
    under a mesh that shards the weights' output features their input
    gradients are summed on the shard (ops/math_ops.py _mxu_fanout)."""
    if len(sizes) == 1:      # nothing to see together: `mul`
        return [fc(input, sizes[0], num_flatten_dims, bias_attr=False,
                   name=name)]
    if input.lod_level:
        raise ValueError("fc_fanout projects a dense input; a ragged one "
                         "goes through fc, one call a projection")
    flat_dim = 1
    for d in input.shape[num_flatten_dims:]:
        flat_dim *= int(d)
    weights, outs = [], []
    for size in sizes:
        # a helper a projection, as a call to fc makes one: the names
        helper = LayerHelper("fc", name=name)
        weights.append(helper.create_parameter(
            helper.param_attr, shape=[flat_dim, size], dtype=input.dtype))
        outs.append(helper.create_tmp_variable(
            input.dtype,
            shape=list(input.shape[:num_flatten_dims]) + [size]))
    helper.append_op(type="fanout_mul", inputs={"X": input, "Y": weights},
                     outputs={"Out": outs},
                     attrs={"x_num_col_dims": num_flatten_dims})
    return outs


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32",
              shard_axis="model"):
    """Embedding lookup (reference: layers/nn.py:218). is_distributed
    row-shards the table over the mesh `shard_axis` and looks up via
    shard_map + psum with row-sparse backward (parallel/sparse.py) —
    the ICI replacement for the reference's pserver sparse path.
    is_sparse is accepted for reference API parity only: on TPU the
    single-chip gradient is a dense scatter-add XLA fuses into the
    step, so the flag has no separate path here."""
    helper = LayerHelper("embedding", param_attr=param_attr)
    w = helper.create_parameter(helper.param_attr, shape=list(size),
                                dtype=dtype)
    out = helper.create_tmp_variable(dtype, lod_level=input.lod_level)
    helper.append_op(type="lookup_table",
                     inputs={"W": w, "Ids": input}, outputs={"Out": out},
                     attrs={"is_sparse": is_sparse,
                            "is_distributed": is_distributed,
                            "shard_axis": shard_axis,
                            "padding_idx": -1 if padding_idx is None
                            else padding_idx})
    return out


def dynamic_lstm(input, size, h_0=None, c_0=None, param_attr=None,
                 bias_attr=None, use_peepholes=False, is_reverse=False,
                 gate_activation="sigmoid", cell_activation="tanh",
                 candidate_activation="tanh", dtype="float32", name=None):
    """Dynamic-length LSTM over a ragged input of gate pre-activations
    [*, 4*hidden] (reference: layers/nn.py:277 / lstm_op.cc)."""
    helper = LayerHelper("dynamic_lstm", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    hidden_size = size // 4
    w = helper.create_parameter(helper.param_attr,
                                shape=[hidden_size, 4 * hidden_size],
                                dtype=dtype)
    bias_size = 4 * hidden_size if not use_peepholes else 7 * hidden_size
    b = helper.create_parameter(helper.bias_attr or ParamAttr(),
                                shape=[1, bias_size], dtype=dtype,
                                is_bias=True)
    hidden = helper.create_tmp_variable(dtype, lod_level=1,
                                        shape=[-1, hidden_size])
    cell = helper.create_tmp_variable(dtype, lod_level=1,
                                      shape=[-1, hidden_size])
    last_h = helper.create_tmp_variable(dtype, shape=[-1, hidden_size])
    last_c = helper.create_tmp_variable(dtype, shape=[-1, hidden_size])
    inputs = {"Input": input, "Weight": w, "Bias": b}
    if h_0 is not None:
        inputs["H0"] = h_0
    if c_0 is not None:
        inputs["C0"] = c_0
    helper.append_op(type="lstm", inputs=inputs,
                     outputs={"Hidden": hidden, "Cell": cell,
                              "LastH": last_h, "LastC": last_c},
                     attrs={"is_reverse": is_reverse,
                            "gate_activation": gate_activation,
                            "cell_activation": cell_activation,
                            "candidate_activation": candidate_activation})
    return hidden, cell


def dynamic_gru(input, size, param_attr=None, bias_attr=None, h_0=None,
                is_reverse=False, gate_activation="sigmoid",
                candidate_activation="tanh", dtype="float32"):
    helper = LayerHelper("dynamic_gru", param_attr=param_attr,
                         bias_attr=bias_attr)
    w = helper.create_parameter(helper.param_attr, shape=[size, 3 * size],
                                dtype=dtype)
    b = helper.create_parameter(helper.bias_attr or ParamAttr(),
                                shape=[1, 3 * size], dtype=dtype,
                                is_bias=True)
    hidden = helper.create_tmp_variable(dtype, lod_level=1,
                                        shape=[-1, size])
    last_h = helper.create_tmp_variable(dtype, shape=[-1, size])
    inputs = {"Input": input, "Weight": w, "Bias": b}
    if h_0 is not None:
        inputs["H0"] = h_0
    helper.append_op(type="gru", inputs=inputs,
                     outputs={"Hidden": hidden, "LastH": last_h},
                     attrs={"is_reverse": is_reverse,
                            "gate_activation": gate_activation,
                            "activation": candidate_activation})
    return hidden


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, act=None,
           use_cudnn=True, name=None):
    """2-D convolution, NCHW (reference: layers/nn.py:1150)."""
    helper = LayerHelper("conv2d", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    num_channels = int(input.shape[1])
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    filter_shape = [num_filters, num_channels // groups] + list(filter_size)
    import math
    fan_in = (num_channels // groups) * filter_size[0] * filter_size[1]
    std = math.sqrt(2.0 / fan_in)
    w = helper.create_parameter(
        helper.param_attr, shape=filter_shape, dtype=dtype,
        default_initializer=NormalInitializer(0.0, std))
    pre_bias = helper.create_tmp_variable(dtype)
    helper.append_op(type="conv2d",
                     inputs={"Input": input, "Filter": w},
                     outputs={"Output": pre_bias},
                     attrs={"strides": _pair(stride),
                            "paddings": _pair(padding),
                            "dilations": _pair(dilation),
                            "groups": groups})
    pre_act = _append_channel_bias(helper, pre_bias, num_filters)
    return helper.append_activation(pre_act)


def _pair(v):
    return list(v) if isinstance(v, (list, tuple)) else [v, v]


def _append_channel_bias(helper, pre_bias, channels=None):
    bias_attr = helper.bias_attr
    if bias_attr is None:
        return pre_bias
    if channels is None:
        channels = int(pre_bias.shape[1]) if pre_bias.shape else None
    b = helper.create_parameter(bias_attr, shape=[channels],
                                dtype=pre_bias.dtype, is_bias=True)
    out = helper.create_tmp_variable(pre_bias.dtype)
    helper.append_op(type="elementwise_add",
                     inputs={"X": pre_bias, "Y": b},
                     outputs={"Out": out}, attrs={"axis": 1})
    return out


def depthwise_conv2d(input, num_filters, filter_size, stride=1, padding=0,
                     param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("depthwise_conv2d", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    filter_shape = [num_filters, 1] + list(filter_size)
    w = helper.create_parameter(helper.param_attr, shape=filter_shape,
                                dtype=dtype)
    pre_bias = helper.create_tmp_variable(dtype)
    helper.append_op(type="depthwise_conv2d",
                     inputs={"Input": input, "Filter": w},
                     outputs={"Output": pre_bias},
                     attrs={"strides": _pair(stride),
                            "paddings": _pair(padding),
                            "dilations": [1, 1]})
    pre_act = _append_channel_bias(helper, pre_bias, num_filters)
    return helper.append_activation(pre_act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, param_attr=None,
                     bias_attr=None, act=None, name=None):
    helper = LayerHelper("conv2d_transpose", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    in_channels = int(input.shape[1])
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    w = helper.create_parameter(
        helper.param_attr, shape=[in_channels, num_filters] + list(
            filter_size), dtype=dtype)
    pre_bias = helper.create_tmp_variable(dtype)
    helper.append_op(type="conv2d_transpose",
                     inputs={"Input": input, "Filter": w},
                     outputs={"Output": pre_bias},
                     attrs={"strides": _pair(stride),
                            "paddings": _pair(padding),
                            "dilations": _pair(dilation)})
    pre_act = _append_channel_bias(helper, pre_bias, num_filters)
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=2, pool_type="max", pool_stride=2,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, name=None, exclusive=True):
    helper = LayerHelper("pool2d", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="pool2d", inputs={"X": input},
                     outputs={"Out": out},
                     attrs={"pooling_type": pool_type,
                            "ksize": _pair(pool_size),
                            "strides": _pair(pool_stride),
                            "paddings": _pair(pool_padding),
                            "global_pooling": global_pooling,
                            "exclusive": exclusive})
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               name=None, moving_mean_name=None, moving_variance_name=None):
    """Batch normalization with persistable moving stats
    (reference: layers/nn.py:1508)."""
    helper = LayerHelper("batch_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    ch = int(input.shape[1] if data_layout == "NCHW" else input.shape[-1])
    scale = helper.create_parameter(
        helper.param_attr, shape=[ch], dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(helper.bias_attr or ParamAttr(),
                                   shape=[ch], dtype=dtype, is_bias=True)
    mean = helper.create_global_variable(
        shape=[ch], dtype=dtype, persistable=True,
        name=moving_mean_name or None)
    helper.set_variable_initializer(mean, ConstantInitializer(0.0))
    variance = helper.create_global_variable(
        shape=[ch], dtype=dtype, persistable=True,
        name=moving_variance_name or None)
    helper.set_variable_initializer(variance, ConstantInitializer(1.0))
    mean.stop_gradient = True
    variance.stop_gradient = True

    saved_mean = helper.create_tmp_variable(dtype)
    saved_var = helper.create_tmp_variable(dtype)
    out = helper.create_tmp_variable(dtype)
    helper.append_op(type="batch_norm",
                     inputs={"X": input, "Scale": scale, "Bias": bias,
                             "Mean": mean, "Variance": variance},
                     outputs={"Y": out, "MeanOut": mean,
                              "VarianceOut": variance,
                              "SavedMean": saved_mean,
                              "SavedVariance": saved_var},
                     attrs={"momentum": momentum, "epsilon": epsilon,
                            "is_test": is_test,
                            "data_layout": data_layout})
    return helper.append_activation(out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    helper = LayerHelper("layer_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    norm_dim = 1
    for d in input.shape[begin_norm_axis:]:
        norm_dim *= int(d)
    inputs = {"X": input}
    if scale:
        s = helper.create_parameter(
            helper.param_attr, shape=[norm_dim], dtype=dtype,
            default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = s
    if shift:
        b = helper.create_parameter(helper.bias_attr or ParamAttr(),
                                    shape=[norm_dim], dtype=dtype,
                                    is_bias=True)
        inputs["Bias"] = b
    out = helper.create_tmp_variable(dtype)
    mean = helper.create_tmp_variable(dtype)
    var = helper.create_tmp_variable(dtype)
    helper.append_op(type="layer_norm", inputs=inputs,
                     outputs={"Y": out, "Mean": mean, "Variance": var},
                     attrs={"epsilon": epsilon,
                            "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(out)


def rms_norm(input, epsilon=1e-6, param_attr=None, name=None):
    """x / sqrt(mean(x^2) + epsilon) * scale over the last axis, scale
    starting at 1 (ops/nn_ops.py rms_norm)."""
    helper = LayerHelper("rms_norm", param_attr=param_attr, name=name)
    scale = helper.create_parameter(
        helper.param_attr, shape=[int(input.shape[-1])], dtype=input.dtype,
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="rms_norm", inputs={"X": input, "Scale": scale},
                     outputs={"Y": out}, attrs={"epsilon": epsilon})
    return out


def rotary_embedding(x, positions, theta=10000.0, rotate_half=False,
                     rotary_dim=None, inv_freq=None, scale=1.0, name=None):
    """Rotary position embedding of x [..., S, w] at the fed positions
    [S] (ops/nn_ops.py rotary_embedding): on neighbouring pairs, or
    ``rotate_half`` on the columns (i, i + r/2); over the first
    ``rotary_dim`` columns (all by default); at theta's frequencies or
    at the ``inv_freq`` [r/2] given, cos and sin times ``scale``. The
    keywords left at their defaults add no attr, so a program that
    uses none of them is built as before."""
    helper = LayerHelper("rotary_embedding", name=name)
    out = helper.create_tmp_variable(x.dtype)
    attrs = {"theta": float(theta)}
    if rotate_half:
        attrs["layout"] = "half"
    if rotary_dim is not None and int(rotary_dim) != int(x.shape[-1]):
        attrs["rotary_dim"] = int(rotary_dim)
    if inv_freq is not None:
        attrs["inv_freq"] = [float(f) for f in inv_freq]
    if float(scale) != 1.0:
        attrs["scale"] = float(scale)
    helper.append_op(type="rotary_embedding",
                     inputs={"X": x, "Positions": positions},
                     outputs={"Out": out}, attrs=attrs)
    return out


def moe_router(input, experts_total, top_k, routed_scaling_factor=1.0,
               selection_bias=None, param_attr=None, name=None):
    """Route input [..., d] over ``experts_total`` experts
    (ops/moe_ops.py moe_router): returns (expert ids [..., top_k] int32,
    weights [..., top_k] float32). ``selection_bias`` (an Initializer)
    adds a NON-trainable per-expert bias that moves the selection and
    never the weights; nothing in the program updates it."""
    helper = LayerHelper("moe_router", param_attr=param_attr, name=name)
    w = helper.create_parameter(
        helper.param_attr, shape=[int(input.shape[-1]), experts_total],
        dtype="float32")
    inputs = {"X": input, "W": w}
    if selection_bias is not None:
        inputs["Bias"] = helper.create_parameter(
            ParamAttr(initializer=selection_bias, trainable=False),
            shape=[experts_total], dtype="float32", is_bias=True)
    idx = helper.create_tmp_variable("int32")
    weights = helper.create_tmp_variable("float32")
    helper.append_op(type="moe_router", inputs=inputs,
                     outputs={"TopIdx": idx, "TopW": weights},
                     attrs={"top_k": int(top_k),
                            "routed_scaling_factor":
                                float(routed_scaling_factor)})
    return idx, weights


def moe_experts(input, top_idx, top_w, d_inner, experts_total,
                experts_held=None, expert_offset=0, down_init_scale=1.0,
                name=None, tally=True):
    """The gated-FFN experts [expert_offset, expert_offset +
    experts_held) of a layer of ``experts_total``, applied to the tokens
    the routing (top_idx, top_w) sends them (ops/moe_ops.py
    moe_experts); every expert where ``experts_held`` is None. The held
    experts' matrices are stacked by rows ([held * d, f] gate and up,
    [held * f, d] down) and drawn as ``held`` separate Xavier layers,
    the down projections times ``down_init_scale``. The layer keeps a
    persistable ``<name>.live_rows`` [4] that no optimizer touches: the
    rows the routing sent its experts, summed over the steps run, the
    steps, the last step's rows (what the grouped products' time
    follows), and the rows of the buffer the step's other passes ran
    over, summed over the steps (the blocks of ops/moe_ops.py
    block_rows that held the live rows; nothing else in the program
    shows either) — unless ``tally`` is False: a served program is
    frozen and writes no persistable but its slots' state
    (models/cca_moe.py counts its rows in the router)."""
    helper = LayerHelper("moe_experts", name=name)
    held = experts_total if experts_held is None else int(experts_held)
    d, f = int(input.shape[-1]), int(d_inner)

    def stacked(rows, cols, scale=1.0):
        limit = scale * (6.0 / (rows + cols)) ** 0.5
        return helper.create_parameter(
            ParamAttr(initializer=UniformInitializer(-limit, limit)),
            shape=[held * rows, cols], dtype=input.dtype)

    w_gate, w_up = stacked(d, f), stacked(d, f)
    w_down = stacked(f, d, down_init_scale)
    out = helper.create_tmp_variable(input.dtype)
    live = helper.create_tmp_variable("float32")
    buffer_rows = helper.create_tmp_variable("float32")
    live.stop_gradient = buffer_rows.stop_gradient = True
    helper.append_op(type="moe_experts",
                     inputs={"X": input, "TopIdx": top_idx, "TopW": top_w,
                             "WGate": w_gate, "WUp": w_up,
                             "WDown": w_down},
                     outputs={"Out": out, "LiveRows": live,
                              "BufferRows": buffer_rows},
                     attrs={"experts_total": int(experts_total),
                            "experts_held": held,
                            "expert_offset": int(expert_offset),
                            "top_k": int(top_idx.shape[-1])})
    if not tally:
        return out
    tally = helper.create_global_variable(
        shape=[4], dtype="float32", persistable=True,
        name=helper.name + ".live_rows")
    helper.set_variable_initializer(tally, ConstantInitializer(0.0))
    helper.append_op(type="moe_rows_tally",
                     inputs={"Tally": tally, "LiveRows": live,
                             "BufferRows": buffer_rows},
                     outputs={"TallyOut": tally})
    return out


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_tmp_variable(x.dtype, lod_level=x.lod_level)
    mask = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="dropout", inputs={"X": x},
                     outputs={"Out": out, "Mask": mask},
                     attrs={"dropout_prob": dropout_prob,
                            "is_test": is_test,
                            "seed": seed or helper.main_program.desc.next_seed(),
                            "dropout_implementation": dropout_implementation})
    return out


def random_crop(x, shape, pad=0, seed=None, name=None):
    """Per-sample random spatial crop of an NCHW batch to
    ``shape=[h, w]`` after zero-padding ``pad`` on each spatial edge
    (ops/augment_ops.py — runs on device where XLA fuses it into the
    step). Deterministic under the program seed."""
    helper = LayerHelper("random_crop", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="random_crop", inputs={"X": x},
                     outputs={"Out": out},
                     attrs={"shape": list(shape), "pad": int(pad),
                            "seed":
                                seed or helper.main_program.desc.next_seed()})
    return out


def random_flip(x, prob=0.5, seed=None, name=None):
    """Per-sample horizontal flip (last axis) with probability `prob`
    (ops/augment_ops.py)."""
    helper = LayerHelper("random_flip", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="random_flip", inputs={"X": x},
                     outputs={"Out": out},
                     attrs={"prob": float(prob),
                            "seed":
                                seed or helper.main_program.desc.next_seed()})
    return out


def image_normalize(x, mean, std, scale=1.0, dtype="float32", name=None):
    """Per-channel ``(x * scale - mean) / std`` for NCHW batches,
    emitting `dtype` ("bfloat16" = the TPU training path). Feed the
    reader's raw uint8 batch straight in: the float conversion happens
    on device (ops/augment_ops.py), not on the input-pipeline host."""
    helper = LayerHelper("image_normalize", name=name)
    out = helper.create_tmp_variable(dtype)
    helper.append_op(type="image_normalize", inputs={"X": x},
                     outputs={"Out": out},
                     attrs={"mean": [float(m) for m in mean],
                            "std": [float(s) for s in std],
                            "scale": float(scale), "dtype": dtype})
    return out


def augment_image(x, crop_shape=None, pad=0, flip_prob=0.5,
                  mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225),
                  scale=1.0 / 255.0, dtype="float32", is_test=False):
    """The standard train-time image augmentation chain as device ops:
    [random_crop] -> random_flip -> image_normalize. With is_test=True
    the random stages are skipped (center behaviour: no crop offset
    support — pass crop_shape=None and pre-sized eval batches)."""
    if not is_test:
        if crop_shape is not None:
            x = random_crop(x, crop_shape, pad=pad)
        if flip_prob > 0:
            x = random_flip(x, prob=flip_prob)
    return image_normalize(x, mean, std, scale=scale, dtype=dtype)


def cross_entropy(input, label, soft_label=False):
    helper = LayerHelper("cross_entropy")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="cross_entropy",
                     inputs={"X": input, "Label": label},
                     outputs={"Y": out}, attrs={"soft_label": soft_label})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False):
    helper = LayerHelper("softmax_with_cross_entropy")
    softmax = helper.create_tmp_variable(logits.dtype)
    loss = helper.create_tmp_variable(logits.dtype)
    helper.append_op(type="softmax_with_cross_entropy",
                     inputs={"Logits": logits, "Label": label},
                     outputs={"Softmax": softmax, "Loss": loss},
                     attrs={"soft_label": soft_label})
    return loss


def sigmoid_cross_entropy_with_logits(x, label, name=None):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="sigmoid_cross_entropy_with_logits",
                     inputs={"X": x, "Label": label}, outputs={"Out": out})
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="square_error_cost",
                     inputs={"X": input, "Y": label}, outputs={"Out": out})
    return out


def accuracy(input, label, k=1, correct=None, total=None):
    """Classification accuracy (reference: layers/nn.py accuracy via
    accuracy_op.cc): top-k over logits then compare with labels."""
    helper = LayerHelper("accuracy")
    topk_out = helper.create_tmp_variable(input.dtype)
    topk_indices = helper.create_tmp_variable("int64")
    helper.append_op(type="top_k", inputs={"X": input},
                     outputs={"Out": topk_out, "Indices": topk_indices},
                     attrs={"k": k})
    acc_out = helper.create_tmp_variable("float32")
    correct = correct or helper.create_tmp_variable("int32")
    total = total or helper.create_tmp_variable("int32")
    helper.append_op(type="accuracy",
                     inputs={"Out": topk_out, "Indices": topk_indices,
                             "Label": label},
                     outputs={"Accuracy": acc_out, "Correct": correct,
                              "Total": total})
    return acc_out


def auc(input, label, curve="ROC", num_thresholds=200):
    helper = LayerHelper("auc")
    auc_out = helper.create_tmp_variable("float32")
    tp = helper.create_tmp_variable("float32")
    fp = helper.create_tmp_variable("float32")
    tn = helper.create_tmp_variable("float32")
    fn = helper.create_tmp_variable("float32")
    helper.append_op(type="auc",
                     inputs={"Predict": input, "Label": label},
                     outputs={"AUC": auc_out, "TPOut": tp, "FPOut": fp,
                              "TNOut": tn, "FNOut": fn},
                     attrs={"num_thresholds": num_thresholds})
    return auc_out


def topk(input, k):
    helper = LayerHelper("top_k")
    values = helper.create_tmp_variable(input.dtype)
    indices = helper.create_tmp_variable("int64")
    helper.append_op(type="top_k", inputs={"X": input},
                     outputs={"Out": values, "Indices": indices},
                     attrs={"k": k})
    return values, indices


# -- sequence layers --------------------------------------------------------

def sequence_pool(input, pool_type):
    helper = LayerHelper("sequence_pool")
    # reduction keeps the per-step feature shape
    out = helper.create_tmp_variable(
        input.dtype, shape=list(input.shape) if input.shape else None)
    # both spellings circulate: fluid pool2d-style "avg", v2 "average"
    ptype = {"AVG": "AVERAGE"}.get(pool_type.upper(), pool_type.upper())
    helper.append_op(type="sequence_pool", inputs={"X": input},
                     outputs={"Out": out},
                     attrs={"pooltype": ptype})
    return out


def sequence_first_step(input):
    helper = LayerHelper("sequence_first_step")
    out = helper.create_tmp_variable(
        input.dtype, shape=list(input.shape) if input.shape else None)
    helper.append_op(type="sequence_first_step", inputs={"X": input},
                     outputs={"Out": out})
    return out


def sequence_last_step(input):
    helper = LayerHelper("sequence_last_step")
    out = helper.create_tmp_variable(
        input.dtype, shape=list(input.shape) if input.shape else None)
    helper.append_op(type="sequence_last_step", inputs={"X": input},
                     outputs={"Out": out})
    return out


def sequence_reverse(x, name=None):
    """Reverse each sequence's valid steps (reference:
    sequence_reverse_op.h)."""
    helper = LayerHelper("sequence_reverse", name=name)
    out = helper.create_tmp_variable(x.dtype, lod_level=1)
    helper.append_op(type="sequence_reverse", inputs={"X": x},
                     outputs={"Y": out})
    return out


def nested_sequence_flatten(input):
    """Nested ragged -> one level shallower (level-2
    paragraph->sentence->token becomes a level-1 batch of sub-sequences;
    deeper LoD peels one level per call). See ops/sequence_ops.py."""
    helper = LayerHelper("nested_sequence_flatten")
    out = helper.create_tmp_variable(
        input.dtype, lod_level=max(1, (input.lod_level or 2) - 1))
    helper.append_op(type="nested_sequence_flatten", inputs={"X": input},
                     outputs={"Out": out})
    return out


def nested_sequence_pack(input, ref):
    """Per-sub-sequence dense rows -> level-1 ragged over the outer level
    of `ref` (a level-2 ragged variable)."""
    helper = LayerHelper("nested_sequence_pack")
    # batch dim becomes the outer level; feature dims carry over (shape
    # inference can't see that input's batch is n*max_sub of ref)
    shape = ([-1] + list(input.shape[1:])) if input.shape else None
    out = helper.create_tmp_variable(input.dtype, lod_level=1, shape=shape)
    helper.append_op(type="nested_sequence_pack",
                     inputs={"X": input, "Ref": ref},
                     outputs={"Out": out})
    return out


def sequence_conv(input, num_filters, filter_size=3, filter_stride=1,
                  padding=None, bias_attr=None, param_attr=None, act=None):
    helper = LayerHelper("sequence_conv", param_attr=param_attr,
                         bias_attr=bias_attr, act=act)
    dtype = input.dtype
    in_dim = int(input.shape[-1])
    w = helper.create_parameter(helper.param_attr,
                                shape=[filter_size * in_dim, num_filters],
                                dtype=dtype)
    pre_bias = helper.create_tmp_variable(dtype, lod_level=1)
    helper.append_op(type="sequence_conv",
                     inputs={"X": input, "Filter": w},
                     outputs={"Out": pre_bias},
                     attrs={"contextLength": filter_size,
                            "contextStart": -(filter_size // 2),
                            "contextStride": filter_stride})
    pre_act = helper.append_bias_op(pre_bias, size=num_filters)
    return helper.append_activation(pre_act)


def sequence_softmax(input):
    helper = LayerHelper("sequence_softmax")
    out = helper.create_tmp_variable(input.dtype, lod_level=1)
    helper.append_op(type="sequence_softmax", inputs={"X": input},
                     outputs={"Out": out})
    return out


def sequence_expand(x, y, ref_level=-1):
    helper = LayerHelper("sequence_expand")
    out = helper.create_tmp_variable(x.dtype, lod_level=1)
    helper.append_op(type="sequence_expand", inputs={"X": x, "Y": y},
                     outputs={"Out": out}, attrs={"ref_level": ref_level})
    return out


def sequence_reshape(input, new_dim):
    helper = LayerHelper("sequence_reshape")
    out = helper.create_tmp_variable(input.dtype, lod_level=1)
    helper.append_op(type="sequence_reshape", inputs={"X": input},
                     outputs={"Out": out}, attrs={"new_dim": new_dim})
    return out


def sequence_mask(x, maxlen, dtype="float32"):
    helper = LayerHelper("sequence_mask")
    out = helper.create_tmp_variable(dtype)
    helper.append_op(type="sequence_mask", inputs={"X": x},
                     outputs={"Y": out}, attrs={"maxlen": maxlen})
    return out


def sequence_pad(x, pad_value=None, maxlen=None):
    helper = LayerHelper("sequence_pad")
    out = helper.create_tmp_variable(x.dtype)
    length = helper.create_tmp_variable("int64")
    helper.append_op(type="sequence_pad", inputs={"X": x},
                     outputs={"Out": out, "Length": length})
    return out, length


def sequence_unpad(x, length):
    helper = LayerHelper("sequence_unpad")
    out = helper.create_tmp_variable(x.dtype, lod_level=1)
    helper.append_op(type="sequence_unpad",
                     inputs={"X": x, "Length": length},
                     outputs={"Out": out})
    return out


def im2sequence(input, filter_size=1, stride=1, padding=0, name=None):
    helper = LayerHelper("im2sequence", name=name)
    out = helper.create_tmp_variable(input.dtype, lod_level=1)
    helper.append_op(type="im2sequence", inputs={"X": input},
                     outputs={"Out": out},
                     attrs={"kernels": _pair(filter_size),
                            "strides": _pair(stride),
                            "paddings": _pair(padding)})
    return out


def row_conv(input, future_context_size, param_attr=None, act=None):
    helper = LayerHelper("row_conv", param_attr=param_attr, act=act)
    dtype = input.dtype
    d = int(input.shape[-1])
    w = helper.create_parameter(helper.param_attr,
                                shape=[future_context_size + 1, d],
                                dtype=dtype)
    out = helper.create_tmp_variable(dtype, lod_level=1)
    helper.append_op(type="row_conv",
                     inputs={"X": input, "Filter": w},
                     outputs={"Out": out})
    return helper.append_activation(out)


# -- math wrappers ----------------------------------------------------------

def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None,
           out_dtype=None):
    """``out_dtype`` (left out of the op where None): the width the
    product is accumulated to AND returned at, whatever the operands'
    — float32 logits from a bfloat16 head."""
    helper = LayerHelper("matmul", name=name)
    out = helper.create_tmp_variable(out_dtype or x.dtype)
    attrs = {"transpose_X": transpose_x, "transpose_Y": transpose_y,
             "alpha": alpha}
    if out_dtype is not None:
        attrs["out_dtype"] = out_dtype
    helper.append_op(type="matmul", inputs={"X": x, "Y": y},
                     outputs={"Out": out}, attrs=attrs)
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1):
    helper = LayerHelper("mul")
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="mul", inputs={"X": x, "Y": y},
                     outputs={"Out": out},
                     attrs={"x_num_col_dims": x_num_col_dims,
                            "y_num_col_dims": y_num_col_dims})
    return out


def _unary(op_type):
    def fn(x, name=None):
        helper = LayerHelper(op_type, name=name)
        out = helper.create_tmp_variable(x.dtype, lod_level=x.lod_level)
        helper.append_op(type=op_type, inputs={"X": x},
                         outputs={"Out": out})
        return out
    fn.__name__ = op_type
    return fn


relu = _unary("relu")
gelu = _unary("gelu")


def softmax(input, axis=-1, name=None):
    helper = LayerHelper("softmax", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="softmax", inputs={"X": input},
                     outputs={"Out": out}, attrs={"axis": axis})
    return out


def log_softmax(input, axis=-1):
    helper = LayerHelper("log_softmax")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="log_softmax", inputs={"X": input},
                     outputs={"Out": out}, attrs={"axis": axis})
    return out


def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    out = helper.create_tmp_variable(x.dtype, shape=[1])
    helper.append_op(type="mean", inputs={"X": x}, outputs={"Out": out})
    return out


def _reduce(op_type):
    def fn(input, dim=None, keep_dim=False, name=None):
        helper = LayerHelper(op_type, name=name)
        out = helper.create_tmp_variable(input.dtype)
        helper.append_op(type=op_type, inputs={"X": input},
                         outputs={"Out": out},
                         attrs={"dim": dim, "keep_dim": keep_dim,
                                "reduce_all": dim is None})
        return out
    fn.__name__ = op_type
    return fn


reduce_sum = _reduce("reduce_sum")
reduce_mean = _reduce("reduce_mean")
reduce_max = _reduce("reduce_max")
reduce_min = _reduce("reduce_min")
reduce_prod = _reduce("reduce_prod")


def _binary(op_type):
    def fn(x, y, axis=-1, act=None, name=None):
        helper = LayerHelper(op_type, act=act, name=name)
        out = helper.create_tmp_variable(x.dtype, lod_level=x.lod_level)
        helper.append_op(type=op_type, inputs={"X": x, "Y": y},
                         outputs={"Out": out}, attrs={"axis": axis})
        return helper.append_activation(out)
    fn.__name__ = op_type
    return fn


elementwise_add = _binary("elementwise_add")
elementwise_sub = _binary("elementwise_sub")
elementwise_mul = _binary("elementwise_mul")
elementwise_div = _binary("elementwise_div")
elementwise_max = _binary("elementwise_max")
elementwise_min = _binary("elementwise_min")
elementwise_pow = _binary("elementwise_pow")


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None,
          name=None):
    helper = LayerHelper("scale", act=act, name=name)
    out = helper.create_tmp_variable(x.dtype, lod_level=x.lod_level)
    helper.append_op(type="scale", inputs={"X": x}, outputs={"Out": out},
                     attrs={"scale": float(scale), "bias": float(bias),
                            "bias_after_scale": bias_after_scale})
    return helper.append_activation(out)


def increment(x, value=1.0, in_place=True):
    helper = LayerHelper("increment")
    out = x if in_place else helper.create_tmp_variable(x.dtype)
    helper.append_op(type="increment", inputs={"X": x},
                     outputs={"Out": out}, attrs={"step": float(value)})
    return out


def cumsum(x, axis=-1, exclusive=False, reverse=False):
    helper = LayerHelper("cumsum")
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="cumsum", inputs={"X": x}, outputs={"Out": out},
                     attrs={"axis": axis, "exclusive": exclusive,
                            "reverse": reverse})
    return out


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="clip", inputs={"X": x}, outputs={"Out": out},
                     attrs={"min": float(min), "max": float(max)})
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="clip_by_norm", inputs={"X": x},
                     outputs={"Out": out},
                     attrs={"max_norm": float(max_norm)})
    return out


def l2_normalize(x, axis=-1, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_tmp_variable(x.dtype)
    norm = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="l2_normalize", inputs={"X": x},
                     outputs={"Out": out, "Norm": norm},
                     attrs={"axis": axis, "epsilon": epsilon})
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", name=name)
    out = helper.create_tmp_variable(input.dtype)
    mid = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="lrn", inputs={"X": input},
                     outputs={"Out": out, "MidOut": mid},
                     attrs={"n": n, "k": k, "alpha": alpha, "beta": beta})
    return out


def cos_sim(x, y):
    helper = LayerHelper("cos_sim")
    out = helper.create_tmp_variable(x.dtype)
    xn = helper.create_tmp_variable(x.dtype)
    yn = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="cos_sim", inputs={"X": x, "Y": y},
                     outputs={"Out": out, "XNorm": xn, "YNorm": yn})
    return out


def dot(x, y):
    helper = LayerHelper("dot")
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="dot", inputs={"X": x, "Y": y},
                     outputs={"Out": out})
    return out


def maxout(x, groups, name=None):
    helper = LayerHelper("maxout", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="maxout", inputs={"X": x}, outputs={"Out": out},
                     attrs={"groups": groups})
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="flatten", inputs={"X": x}, outputs={"Out": out},
                     attrs={"axis": axis})
    return out


def _act_layer(op_type, **default_attrs):
    def fn(x, name=None, **kw):
        helper = LayerHelper(op_type, name=name)
        out = helper.create_tmp_variable(x.dtype)
        attrs = dict(default_attrs)
        attrs.update(kw)
        helper.append_op(type=op_type, inputs={"X": x},
                         outputs={"Out": out}, attrs=attrs)
        return out
    fn.__name__ = op_type
    return fn


leaky_relu = _act_layer("leaky_relu", alpha=0.02)
elu = _act_layer("elu", alpha=1.0)
pow = _act_layer("pow", factor=1.0)
swish = _act_layer("swish", beta=1.0)
hard_sigmoid = _act_layer("hard_sigmoid", slope=0.2, offset=0.5)
relu6 = _act_layer("relu6")
soft_relu = _act_layer("soft_relu", threshold=40.0)


def pad2d(input, paddings=(0, 0, 0, 0), mode="constant", pad_value=0.0,
          name=None):
    helper = LayerHelper("pad2d", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="pad2d", inputs={"X": input},
                     outputs={"Out": out},
                     attrs={"paddings": list(paddings), "mode": mode,
                            "pad_value": float(pad_value)})
    return out


# -- losses / misc ----------------------------------------------------------

def smooth_l1(x, y, sigma=1.0):
    helper = LayerHelper("smooth_l1_loss")
    diff = helper.create_tmp_variable(x.dtype)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="smooth_l1_loss", inputs={"X": x, "Y": y},
                     outputs={"Diff": diff, "Out": out},
                     attrs={"sigma": sigma})
    return out


def huber_loss(input, label, delta=1.0):
    helper = LayerHelper("huber_loss")
    residual = helper.create_tmp_variable(input.dtype)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="huber_loss",
                     inputs={"X": input, "Y": label},
                     outputs={"Residual": residual, "Out": out},
                     attrs={"delta": delta})
    return out


def log_loss(input, label, epsilon=1e-4):
    helper = LayerHelper("log_loss")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="log_loss",
                     inputs={"Predicted": input, "Labels": label},
                     outputs={"Loss": out}, attrs={"epsilon": epsilon})
    return out


def kldiv_loss(x, target, reduction="mean"):
    helper = LayerHelper("kldiv_loss")
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="kldiv_loss",
                     inputs={"X": x, "Target": target},
                     outputs={"Loss": out}, attrs={"reduction": reduction})
    return out


def margin_rank_loss(label, left, right, margin=0.1):
    helper = LayerHelper("margin_rank_loss")
    out = helper.create_tmp_variable(left.dtype)
    act = helper.create_tmp_variable(left.dtype)
    helper.append_op(type="margin_rank_loss",
                     inputs={"X1": left, "X2": right, "Label": label},
                     outputs={"Out": out, "Activated": act},
                     attrs={"margin": margin})
    return out


def hinge_loss(logits, labels):
    helper = LayerHelper("hinge_loss")
    out = helper.create_tmp_variable(logits.dtype)
    helper.append_op(type="hinge_loss",
                     inputs={"Logits": logits, "Labels": labels},
                     outputs={"Loss": out})
    return out


def edit_distance(input, label, normalized=False):
    helper = LayerHelper("edit_distance")
    out = helper.create_tmp_variable("float32")
    seq_num = helper.create_tmp_variable("int64")
    helper.append_op(type="edit_distance",
                     inputs={"Hyps": input, "Refs": label},
                     outputs={"Out": out, "SequenceNum": seq_num},
                     attrs={"normalized": normalized})
    return out, seq_num


def nce(input, label, num_total_classes, sample_weight=None,
        param_attr=None, bias_attr=None, num_neg_samples=10):
    helper = LayerHelper("nce", param_attr=param_attr, bias_attr=bias_attr)
    dim = int(input.shape[-1])
    w = helper.create_parameter(helper.param_attr,
                                shape=[num_total_classes, dim],
                                dtype=input.dtype)
    b = helper.create_parameter(helper.bias_attr or ParamAttr(),
                                shape=[num_total_classes],
                                dtype=input.dtype, is_bias=True)
    cost = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="nce",
                     inputs={"Input": input, "Label": label, "Weight": w,
                             "Bias": b},
                     outputs={"Cost": cost},
                     attrs={"num_neg_samples": num_neg_samples,
                            "seed": helper.main_program.desc.next_seed()})
    return cost


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None):
    """Hierarchical sigmoid via a complete binary tree over classes
    (reference: hierarchical_sigmoid_op.cc) — composed from dense ops."""
    import math
    helper = LayerHelper("hsigmoid", param_attr=param_attr,
                         bias_attr=bias_attr)
    # Simplified capability-parity implementation: logistic ova reduction.
    dim = int(input.shape[-1])
    w = helper.create_parameter(helper.param_attr,
                                shape=[dim, num_classes], dtype=input.dtype)
    logits = mul(input, w)
    lbl = one_hot_v2(label, num_classes)
    loss = sigmoid_cross_entropy_with_logits(logits, lbl)
    return reduce_sum(loss, dim=1, keep_dim=True)


def one_hot_v2(input, depth):
    helper = LayerHelper("one_hot")
    out = helper.create_tmp_variable("float32")
    helper.append_op(type="one_hot", inputs={"X": input},
                     outputs={"Out": out}, attrs={"depth": depth})
    return out


def warpctc(input, label, blank=0, norm_by_times=False):
    """CTC loss over ragged logits/labels (reference: warpctc_op.cc wraps
    the warp-ctc CUDA lib; here a pure-XLA dynamic-program)."""
    helper = LayerHelper("warpctc")
    loss = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="warpctc",
                     inputs={"Logits": input, "Label": label},
                     outputs={"Loss": loss},
                     attrs={"blank": blank, "norm_by_times": norm_by_times})
    return loss


def scaled_dot_product_attention(queries, keys, values, num_heads=1,
                                 dropout_rate=0.0):
    helper = LayerHelper("scaled_dot_product_attention")
    shape = None
    if queries.shape is not None and values.shape is not None:
        shape = list(queries.shape[:-1]) + [values.shape[-1]]
    out = helper.create_tmp_variable(queries.dtype,
                                     lod_level=queries.lod_level,
                                     shape=shape)
    helper.append_op(type="scaled_dot_product_attention",
                     inputs={"Q": queries, "K": keys, "V": values},
                     outputs={"Out": out})
    return out


def beam_search(pre_ids, pre_scores, ids, scores, beam_size, end_id,
                level=0, is_accumulated=False):
    """One beam-search expansion step (reference: beam_search_op.cc),
    fixed-beam dense form: scores [batch, beam, cand] (or flat
    [batch*beam, cand]) step log-probs; totals accumulate against
    pre_scores unless `is_accumulated`. Finished lanes (pre_id ==
    end_id) are frozen instead of pruned — see ops/beam_search_ops.py.
    Initialize pre_scores to 0 for lane 0 and a large negative value
    for other lanes so identical initial beams don't duplicate."""
    helper = LayerHelper("beam_search")
    selected_ids = helper.create_tmp_variable(ids.dtype)
    selected_scores = helper.create_tmp_variable(scores.dtype)
    parent_idx = helper.create_tmp_variable("int32")
    inputs = {"pre_ids": pre_ids, "ids": ids, "scores": scores}
    if pre_scores is not None:
        inputs["pre_scores"] = pre_scores
    helper.append_op(type="beam_search",
                     inputs=inputs,
                     outputs={"selected_ids": selected_ids,
                              "selected_scores": selected_scores,
                              "parent_idx": parent_idx},
                     attrs={"beam_size": beam_size, "end_id": end_id,
                            "is_accumulated": is_accumulated})
    return selected_ids, selected_scores, parent_idx


def beam_search_decode(ids, scores, beam_size, end_id, parents=None,
                       length=None):
    """Backtrack beam-search step arrays into sentences (reference:
    beam_search_decode_op.cc). `ids`/`scores`/`parents` are the stacked
    step arrays ([T, ...]); `length` the valid-step count. Outputs
    SentenceIds [batch, beam, T] (end_id padded) + SentenceScores
    [batch, beam], best beam first. When `length` is omitted the FULL
    array capacity is decoded — only correct for exactly-sized arrays;
    loop-built arrays must pass their step counter."""
    if parents is not None and length is None:
        raise ValueError(
            "beam_search_decode: parents implies a decode loop whose "
            "arrays are capacity-padded; pass length= (the step counter) "
            "or unwritten slots would be decoded as real steps")
    helper = LayerHelper("beam_search_decode")
    sentence_ids = helper.create_tmp_variable(ids.dtype)
    sentence_scores = helper.create_tmp_variable(scores.dtype)
    inputs = {"Ids": ids, "Scores": scores}
    if parents is not None:
        inputs["ParentIdx"] = parents
    if length is not None:
        inputs["Length"] = length
    helper.append_op(type="beam_search_decode",
                     inputs=inputs,
                     outputs={"SentenceIds": sentence_ids,
                              "SentenceScores": sentence_scores},
                     attrs={"beam_size": beam_size, "end_id": end_id})
    return sentence_ids, sentence_scores


def linear_chain_crf(input, label, param_attr=None):
    """Linear-chain CRF negative log-likelihood over ragged sequences
    (reference: layers/nn.py linear_chain_crf / linear_chain_crf_op.cc).
    Creates the [num_tags+2, num_tags] transition parameter (rows 0/1 =
    start/end weights)."""
    helper = LayerHelper("linear_chain_crf", param_attr=param_attr)
    num_tags = int(input.shape[-1])
    transition = helper.create_parameter(
        helper.param_attr, shape=[num_tags + 2, num_tags],
        dtype=input.dtype)
    ll = helper.create_tmp_variable(input.dtype)
    alpha = helper.create_tmp_variable(input.dtype)
    em_exps = helper.create_tmp_variable(input.dtype)
    tr_exps = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="linear_chain_crf",
                     inputs={"Emission": input, "Label": label,
                             "Transition": transition},
                     outputs={"LogLikelihood": ll, "Alpha": alpha,
                              "EmissionExps": em_exps,
                              "TransitionExps": tr_exps})
    return ll


def crf_decoding(input, param_attr=None, label=None):
    """Viterbi decode with a trained CRF transition parameter (reference:
    layers/nn.py crf_decoding / crf_decoding_op.h). With `label`, emits
    per-position 0/1 correctness instead of the path."""
    helper = LayerHelper("crf_decoding", param_attr=param_attr)
    attr = helper.param_attr
    num_tags = int(input.shape[-1])
    if attr is not None and attr.name is not None and \
            helper.main_program.global_block().has_var(attr.name):
        # Share the transition parameter trained by linear_chain_crf.
        transition = helper.main_program.global_block().var(attr.name)
    else:
        # Decode-only/inference programs create it fresh (it is then
        # loaded from a checkpoint by name).
        transition = helper.create_parameter(
            attr, shape=[num_tags + 2, num_tags], dtype=input.dtype)
    path = helper.create_tmp_variable("int64", lod_level=input.lod_level)
    inputs = {"Emission": input, "Transition": transition}
    if label is not None:
        inputs["Label"] = label
    helper.append_op(type="crf_decoding", inputs=inputs,
                     outputs={"ViterbiPath": path})
    return path


def multiplex(inputs, index):
    """Select rows among candidates by index (reference: nn.py multiplex)."""
    helper = LayerHelper("multiplex")
    out = helper.create_tmp_variable(inputs[0].dtype)
    helper.append_op(type="multiplex",
                     inputs={"X": list(inputs), "Ids": index},
                     outputs={"Out": out})
    return out


# -- single-step RNN cells (reference: nn.py lstm_unit:  gru_unit:) ---------

def lstm_unit(x_t, hidden_t_prev, cell_t_prev, forget_bias=0.0,
              param_attr=None, bias_attr=None, name=None):
    """One LSTM step: projects [x_t, h_prev] to 4*d gates then applies the
    cell update (reference: nn.py lstm_unit — built on lstm_unit op)."""
    helper = LayerHelper("lstm_unit", name=name)
    d = cell_t_prev.shape[-1]
    concat_in = fc(x_t, size=4 * d, bias_attr=bias_attr,
                   param_attr=param_attr)
    h_proj = fc(hidden_t_prev, size=4 * d, bias_attr=False)
    gates = helper.create_tmp_variable(x_t.dtype)
    helper.append_op(type="elementwise_add",
                     inputs={"X": concat_in, "Y": h_proj},
                     outputs={"Out": gates})
    c = helper.create_tmp_variable(x_t.dtype)
    h = helper.create_tmp_variable(x_t.dtype)
    helper.append_op(type="lstm_unit",
                     inputs={"X": gates, "C_prev": cell_t_prev},
                     outputs={"C": c, "H": h},
                     attrs={"forget_bias": float(forget_bias)})
    return h, c


def gru_unit(input, hidden, size, param_attr=None, bias_attr=None,
             activation="tanh", gate_activation="sigmoid"):
    """One GRU step (reference: nn.py gru_unit). size = 3*d."""
    helper = LayerHelper("gru_unit")
    d = size // 3
    weight = helper.create_parameter(attr=param_attr, shape=[d, 3 * d],
                                     dtype=input.dtype)
    bias = helper.create_parameter(attr=bias_attr, shape=[1, 3 * d],
                                   dtype=input.dtype, is_bias=True)
    gate = helper.create_tmp_variable(input.dtype)
    reset_h = helper.create_tmp_variable(input.dtype)
    hid = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="gru_unit",
                     inputs={"Input": input, "HiddenPrev": hidden,
                             "Weight": weight, "Bias": bias},
                     outputs={"Gate": gate, "ResetHiddenPrev": reset_h,
                              "Hidden": hid},
                     attrs={"activation": activation,
                            "gate_activation": gate_activation})
    return hid, reset_h, gate


def dynamic_lstmp(input, size, proj_size, param_attr=None, bias_attr=None,
                  use_peepholes=False, is_reverse=False,
                  gate_activation="sigmoid", cell_activation="tanh",
                  candidate_activation="tanh", proj_activation="tanh",
                  name=None):
    """Projected LSTM over ragged input (reference: nn.py dynamic_lstmp:
    input already projected to [*, 4*d]; recurrence on the p-dim
    projection)."""
    helper = LayerHelper("dynamic_lstmp", name=name)
    d = size // 4
    weight = helper.create_parameter(attr=param_attr, shape=[proj_size, 4 * d],
                                     dtype=input.dtype)
    proj_weight = helper.create_parameter(attr=param_attr,
                                          shape=[d, proj_size],
                                          dtype=input.dtype)
    # peepholes pack W_ic/W_fc/W_oc after the gate bias (reference layout)
    bias_size = 7 * d if use_peepholes else 4 * d
    bias = helper.create_parameter(attr=bias_attr, shape=[1, bias_size],
                                   dtype=input.dtype, is_bias=True)
    proj = helper.create_tmp_variable(input.dtype, lod_level=input.lod_level)
    cell = helper.create_tmp_variable(input.dtype, lod_level=input.lod_level)
    last_h = helper.create_tmp_variable(input.dtype)
    last_c = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="lstmp",
                     inputs={"Input": input, "Weight": weight,
                             "ProjWeight": proj_weight, "Bias": bias},
                     outputs={"Projection": proj, "Cell": cell,
                              "LastH": last_h, "LastC": last_c},
                     attrs={"gate_activation": gate_activation,
                            "cell_activation": cell_activation,
                            "candidate_activation": candidate_activation,
                            "proj_activation": proj_activation,
                            "use_peepholes": use_peepholes,
                            "is_reverse": is_reverse})
    return proj, cell


# -- decode/eval wrappers ---------------------------------------------------

def ctc_greedy_decoder(input, blank, name=None):
    helper = LayerHelper("ctc_greedy_decoder", name=name)
    out = helper.create_tmp_variable("int32", lod_level=1)
    helper.append_op(type="ctc_greedy_decoder", inputs={"Input": input},
                     outputs={"Out": out}, attrs={"blank": blank})
    return out


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None):
    helper = LayerHelper("chunk_eval")
    precision = helper.create_tmp_variable("float32")
    recall = helper.create_tmp_variable("float32")
    f1 = helper.create_tmp_variable("float32")
    num_infer = helper.create_tmp_variable("int64")
    num_label = helper.create_tmp_variable("int64")
    num_correct = helper.create_tmp_variable("int64")
    helper.append_op(type="chunk_eval",
                     inputs={"Inference": input, "Label": label},
                     outputs={"Precision": precision, "Recall": recall,
                              "F1-Score": f1, "NumInferChunks": num_infer,
                              "NumLabelChunks": num_label,
                              "NumCorrectChunks": num_correct},
                     attrs={"num_chunk_types": num_chunk_types,
                            "chunk_scheme": chunk_scheme,
                            "excluded_chunk_types":
                                list(excluded_chunk_types or [])})
    return precision, recall, f1, num_infer, num_label, num_correct


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """Persistent int64 step counter incremented per run (reference:
    nn.py autoincreased_step_counter)."""
    helper = LayerHelper("global_step_counter")
    counter = helper.create_global_variable(
        shape=[1], dtype="int64", name=counter_name or "@STEP_COUNTER@",
        persistable=True)
    helper.set_variable_initializer(
        counter, ConstantInitializer(float(begin - step)))
    helper.append_op(type="increment", inputs={"X": counter},
                     outputs={"Out": counter}, attrs={"step": float(step)})
    counter.stop_gradient = True
    return counter


def lod_reset(x, y=None, target_lod=None):
    """Reassign sequence boundaries (reference: nn.py lod_reset)."""
    helper = LayerHelper("lod_reset")
    out = helper.create_tmp_variable(x.dtype, lod_level=1)
    inputs = {"X": x}
    if y is not None:
        inputs["Y"] = y
    helper.append_op(type="lod_reset", inputs=inputs, outputs={"Out": out},
                     attrs={"target_lod": list(target_lod or [])})
    return out


def prelu(x, mode="all", param_attr=None, name=None):
    helper = LayerHelper("prelu", name=name)
    alpha_len = x.shape[1] if mode == "channel" else 1
    alpha = helper.create_parameter(attr=param_attr, shape=[alpha_len],
                                    dtype=x.dtype,
                                    default_initializer=ConstantInitializer(
                                        0.25))
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="prelu", inputs={"X": x, "Alpha": alpha},
                     outputs={"Out": out}, attrs={"mode": mode})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_tmp_variable(dtype)
    inputs = {"X": label}
    if prior_dist is not None:
        inputs["PriorDist"] = prior_dist
    helper.append_op(type="label_smooth", inputs=inputs,
                     outputs={"Out": out}, attrs={"epsilon": float(epsilon)})
    return out


def rank_loss(label, left, right, name=None):
    helper = LayerHelper("rank_loss", name=name)
    out = helper.create_tmp_variable("float32")
    helper.append_op(type="rank_loss",
                     inputs={"Label": label, "Left": left, "Right": right},
                     outputs={"Out": out})
    return out


def roi_pool(input, rois, pooled_height=1, pooled_width=1,
             spatial_scale=1.0):
    helper = LayerHelper("roi_pool")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="roi_pool",
                     inputs={"X": input, "ROIs": rois},
                     outputs={"Out": out},
                     attrs={"pooled_height": pooled_height,
                            "pooled_width": pooled_width,
                            "spatial_scale": spatial_scale})
    return out


def _interp_layer(op_type, input, out_shape=None, scale=None, name=None):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_tmp_variable(input.dtype)
    attrs = {}
    if out_shape is not None:
        attrs["out_h"], attrs["out_w"] = int(out_shape[0]), \
            int(out_shape[1])
    if scale is not None:
        attrs["scale"] = float(scale)
    helper.append_op(type=op_type, inputs={"X": input},
                     outputs={"Out": out}, attrs=attrs)
    return out


def bilinear_interp(input, out_shape=None, scale=None, name=None):
    """Bilinear NCHW resize (reference: legacy bilinear_interp layer)."""
    return _interp_layer("bilinear_interp", input, out_shape, scale, name)


def nearest_interp(input, out_shape=None, scale=None, name=None):
    """Nearest-neighbor NCHW resize (reference: legacy upsample/resize)."""
    return _interp_layer("nearest_interp", input, out_shape, scale, name)


resize_bilinear = bilinear_interp


def upsample(input, scale=2, name=None):
    return _interp_layer("nearest_interp", input, None, scale, name)


def sampling_id(x, seed=0, name=None):
    """Sample one id per row from probabilities (reference: sampling_id
    layer; stochastic generation)."""
    helper = LayerHelper("sampling_id", name=name)
    out = helper.create_tmp_variable("int64")
    helper.append_op(type="sampling_id", inputs={"X": x},
                     outputs={"Out": out}, attrs={"seed": seed})
    return out

