"""Tensor-construction and manipulation layers.

Reference parity: python/paddle/fluid/layers/tensor.py (create_tensor,
cast, concat, sums, assign, fill_constant, ones, zeros, reverse...).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Union

import numpy as np

from ..framework import Variable
from ..layer_helper import LayerHelper

__all__ = [
    "create_tensor", "create_parameter", "create_global_var", "cast",
    "concat", "sums", "sum", "assign", "fill_constant",
    "fill_constant_batch_size_like",
    "ones", "zeros", "reverse", "reshape", "transpose", "split", "squeeze",
    "unsqueeze", "stack", "expand", "gather", "scatter", "pad", "one_hot",
    "argmax", "argmin", "shape", "range", "linspace", "zeros_like",
    "ones_like", "diag", "eye", "slice", "Print",
]


def create_tensor(dtype, name=None, persistable=False):
    helper = LayerHelper("create_tensor", name=name)
    return helper.create_variable(name=helper.name, dtype=dtype,
                                  persistable=persistable)


def create_parameter(shape, dtype, name=None, attr=None,
                     is_bias=False, default_initializer=None):
    helper = LayerHelper("create_parameter")
    from ..layer_helper import ParamAttr
    attr = attr or ParamAttr(name=name)
    return helper.create_parameter(attr, shape, dtype, is_bias,
                                   default_initializer)


def create_global_var(shape, value, dtype, persistable=False,
                      force_cpu=False, name=None):
    helper = LayerHelper("global_var", name=name)
    var = helper.create_global_variable(shape=shape, dtype=dtype,
                                        persistable=persistable,
                                        name=name)
    from ..initializer import ConstantInitializer
    helper.set_variable_initializer(var, ConstantInitializer(value))
    return var


def cast(x, dtype):
    helper = LayerHelper("cast")
    out = helper.create_tmp_variable(dtype, lod_level=x.lod_level)
    helper.append_op(type="cast", inputs={"X": x}, outputs={"Out": out},
                     attrs={"out_dtype": dtype})
    return out


def concat(input: Sequence[Variable], axis: int = 0, name=None):
    helper = LayerHelper("concat", name=name)
    shape = None
    ranks = {len(v.shape) for v in input if v.shape is not None}
    if len(ranks) == 1 and all(v.shape is not None for v in input):
        shape = list(input[0].shape)
        ax = axis if axis >= 0 else len(shape) + axis
        if 0 <= ax < len(shape):
            dims = [v.shape[ax] for v in input]
            # builtins.sum: the module-level `sum = sums` layer alias
            # (reference API parity) shadows the builtin here
            import builtins
            shape[ax] = -1 if any(d is None or d < 0 for d in dims) \
                else builtins.sum(dims)
        else:
            # Declared shapes are loose metadata (ragged vars declare 2D);
            # leave it to the runtime op when the axis is out of range.
            shape = None
    out = helper.create_tmp_variable(input[0].dtype,
                                     lod_level=input[0].lod_level,
                                     shape=shape)
    helper.append_op(type="concat", inputs={"X": list(input)},
                     outputs={"Out": out}, attrs={"axis": axis})
    return out


def sums(input: Sequence[Variable], out=None):
    helper = LayerHelper("sums")
    out = out or helper.create_tmp_variable(input[0].dtype)
    helper.append_op(type="sum", inputs={"X": list(input)},
                     outputs={"Out": out})
    return out


# reference layers/ops.py exports `sum` (same op) alongside `sums`
sum = sums  # noqa: A001


def Print(input, first_n=-1, message=None, summarize=-1,  # noqa: N802
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_lod=True,
          print_phase="both"):
    """Debug-print a tensor from inside the compiled program
    (reference: layers/control_flow.py Print over print_op.cc; the
    formatting knobs are accepted for API parity — jax.debug.print
    renders the value)."""
    helper = LayerHelper("print")
    out = helper.create_tmp_variable(input.dtype,
                                     lod_level=input.lod_level,
                                     shape=input.shape)
    helper.append_op(type="print", inputs={"X": input},
                     outputs={"Out": out},
                     attrs={"message": message or input.name})
    return out


def assign(input, output=None):
    helper = LayerHelper("assign")
    if isinstance(input, np.ndarray):
        output = output or helper.create_tmp_variable(str(input.dtype))
        helper.append_op(type="assign_value", outputs={"Out": output},
                         attrs={"shape": list(input.shape),
                                "dtype": str(input.dtype),
                                "values": input.reshape(-1).tolist()})
    else:
        output = output or helper.create_tmp_variable(input.dtype)
        helper.append_op(type="assign", inputs={"X": input},
                         outputs={"Out": output})
    return output


def fill_constant(shape, dtype, value, out=None, name=None):
    helper = LayerHelper("fill_constant", name=name)
    out = out or helper.create_tmp_variable(dtype, shape=list(shape))
    helper.append_op(type="fill_constant", outputs={"Out": out},
                     attrs={"shape": list(shape), "dtype": dtype,
                            "value": float(value)})
    out.stop_gradient = True
    return out


def fill_constant_batch_size_like(input, shape, dtype, value,
                                  input_dim_idx=0, output_dim_idx=0):
    # Static-shape regime: batch dim comes from the input's known shape.
    helper = LayerHelper("fill_constant_batch_size_like")
    out = helper.create_tmp_variable(dtype)
    helper.append_op(type="fill_constant_batch_size_like",
                     inputs={"Input": input}, outputs={"Out": out},
                     attrs={"shape": list(shape), "dtype": dtype,
                            "value": float(value),
                            "input_dim_idx": input_dim_idx,
                            "output_dim_idx": output_dim_idx})
    out.stop_gradient = True
    return out


def ones(shape, dtype="float32"):
    return fill_constant(shape, dtype, 1.0)


def zeros(shape, dtype="float32"):
    return fill_constant(shape, dtype, 0.0)


def zeros_like(x, out=None):
    helper = LayerHelper("zeros_like")
    out = out or helper.create_tmp_variable(x.dtype)
    helper.append_op(type="fill_zeros_like", inputs={"X": x},
                     outputs={"Out": out})
    return out


def ones_like(x, out=None):
    helper = LayerHelper("ones_like")
    out = out or helper.create_tmp_variable(x.dtype)
    helper.append_op(type="fill_constant_like", inputs={"X": x},
                     outputs={"Out": out}, attrs={"value": 1.0})
    return out


def reverse(x, axis):
    helper = LayerHelper("reverse")
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="reverse", inputs={"X": x}, outputs={"Out": out},
                     attrs={"axis": axis if isinstance(axis, (list, tuple))
                            else [axis]})
    return out


def reshape(x, shape, inplace=False, name=None, act=None):
    helper = LayerHelper("reshape", name=name, act=act)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="reshape", inputs={"X": x}, outputs={"Out": out},
                     attrs={"shape": list(shape)})
    return helper.append_activation(out)


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="transpose", inputs={"X": x},
                     outputs={"Out": out}, attrs={"axis": list(perm)})
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        sections = None
    else:
        num = len(num_or_sections)
        sections = list(num_or_sections)
    # builtins.range: this module's `range` layer shadows the builtin
    import builtins
    outs = [helper.create_tmp_variable(input.dtype)
            for _ in builtins.range(num)]
    helper.append_op(type="split", inputs={"X": input},
                     outputs={"Out": outs},
                     attrs={"num": num if sections is None else 0,
                            "sections": sections or [],
                            "axis": dim})
    return outs


def squeeze(input, axes=None, name=None):
    helper = LayerHelper("squeeze", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="squeeze", inputs={"X": input},
                     outputs={"Out": out}, attrs={"axes": axes or []})
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="unsqueeze", inputs={"X": input},
                     outputs={"Out": out}, attrs={"axes": axes})
    return out


def stack(x: Sequence[Variable], axis: int = 0):
    helper = LayerHelper("stack")
    out = helper.create_tmp_variable(x[0].dtype)
    helper.append_op(type="stack", inputs={"X": list(x)},
                     outputs={"Y": out}, attrs={"axis": axis})
    return out


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="expand", inputs={"X": x}, outputs={"Out": out},
                     attrs={"expand_times": list(expand_times)})
    return out


def gather(input, index):
    helper = LayerHelper("gather")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="gather", inputs={"X": input, "Index": index},
                     outputs={"Out": out})
    return out


def scatter(input, index, updates, overwrite=True, name=None):
    helper = LayerHelper("scatter", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="scatter",
                     inputs={"X": input, "Ids": index, "Updates": updates},
                     outputs={"Out": out}, attrs={"overwrite": overwrite})
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="pad", inputs={"X": x}, outputs={"Out": out},
                     attrs={"paddings": list(paddings),
                            "pad_value": float(pad_value)})
    return out


def one_hot(input, depth):
    helper = LayerHelper("one_hot")
    out = helper.create_tmp_variable("float32")
    helper.append_op(type="one_hot", inputs={"X": input},
                     outputs={"Out": out}, attrs={"depth": depth})
    return out


def argmax(x, axis=0):
    helper = LayerHelper("argmax")
    out = helper.create_tmp_variable("int64")
    helper.append_op(type="arg_max", inputs={"X": x}, outputs={"Out": out},
                     attrs={"axis": axis})
    return out


def argmin(x, axis=0):
    helper = LayerHelper("argmin")
    out = helper.create_tmp_variable("int64")
    helper.append_op(type="arg_min", inputs={"X": x}, outputs={"Out": out},
                     attrs={"axis": axis})
    return out


def shape(input):
    helper = LayerHelper("shape")
    out = helper.create_tmp_variable("int64")
    helper.append_op(type="shape", inputs={"X": input},
                     outputs={"Out": out})
    return out


def range(start, end, step, dtype="int64"):
    helper = LayerHelper("range")
    out = helper.create_tmp_variable(dtype)
    helper.append_op(type="range", outputs={"Out": out},
                     attrs={"start": start, "end": end, "step": step})
    return out


def linspace(start, stop, num, dtype="float32"):
    helper = LayerHelper("linspace")
    out = helper.create_tmp_variable(dtype)
    helper.append_op(type="linspace", outputs={"Out": out},
                     attrs={"start": float(start), "stop": float(stop),
                            "num": int(num)})
    return out


def diag(diagonal):
    helper = LayerHelper("diag")
    out = helper.create_tmp_variable(diagonal.dtype)
    helper.append_op(type="diag", inputs={"Diagonal": diagonal},
                     outputs={"Out": out})
    return out


def eye(num_rows, num_columns=None, dtype="float32"):
    helper = LayerHelper("eye")
    out = helper.create_tmp_variable(dtype)
    helper.append_op(type="eye", outputs={"Out": out},
                     attrs={"num_rows": num_rows,
                            "num_columns": num_columns or num_rows})
    return out


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="slice", inputs={"Input": input},
                     outputs={"Out": out},
                     attrs={"axes": list(axes), "starts": list(starts),
                            "ends": list(ends)})
    return out
