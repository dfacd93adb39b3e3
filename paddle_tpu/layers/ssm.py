"""Layer wrappers of the state-space ops (ops/ssm_ops.py). The
parameters and the persistable state are the caller's: a served model
creates them once and hands them to the prefill and the decode program
alike (models/hybrid_ssm.py)."""
from __future__ import annotations

from ..layer_helper import LayerHelper

__all__ = ["ssd_prefill", "ssm_state_update", "causal_conv1d",
           "conv_state_update", "slot_state_write"]


def ssd_prefill(x, dt, b, c, a_log, dt_bias, d, length, chunk=256):
    """x [n, S, H * P], dt [n, S, H], b, c [n, S, N], length [n] ->
    (y [n, S, H * P], final state [n, N, H * P] float32)."""
    helper = LayerHelper("ssd_prefill")
    y = helper.create_tmp_variable(x.dtype)
    state = helper.create_tmp_variable("float32")
    helper.append_op(
        type="ssd_prefill",
        inputs={"X": x, "Dt": dt, "B": b, "C": c, "ALog": a_log,
                "DtBias": dt_bias, "D": d, "Length": length},
        outputs={"Y": y, "State": state}, attrs={"chunk": int(chunk)})
    return y, state


def ssm_state_update(state, x, dt, b, c, a_log, dt_bias, d):
    """One token a slot; ``state`` (persistable) is updated in place."""
    helper = LayerHelper("ssm_state_update")
    y = helper.create_tmp_variable(x.dtype)
    helper.append_op(
        type="ssm_state_update",
        inputs={"State": state, "X": x, "Dt": dt, "B": b, "C": c,
                "ALog": a_log, "DtBias": dt_bias, "D": d},
        outputs={"Y": y, "StateOut": state}, attrs={})
    return y


def causal_conv1d(x, w, bias, length):
    """x [n, S, C], w [K, C], bias [C], length [n] -> (out [n, S, C],
    the last K - 1 real inputs [n, (K - 1) * C])."""
    helper = LayerHelper("causal_conv1d")
    out = helper.create_tmp_variable(x.dtype)
    state = helper.create_tmp_variable(x.dtype)
    helper.append_op(
        type="causal_conv1d",
        inputs={"X": x, "W": w, "Bias": bias, "Length": length},
        outputs={"Out": out, "State": state}, attrs={})
    return out, state


def conv_state_update(state, x, w, bias):
    """One token a slot; ``state`` (persistable) is updated in place."""
    helper = LayerHelper("conv_state_update")
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(
        type="conv_state_update",
        inputs={"State": state, "X": x, "W": w, "Bias": bias},
        outputs={"Out": out, "StateOut": state}, attrs={})
    return out


def slot_state_write(state, new, slot):
    """``new`` [1, ...] into row ``slot`` of ``state`` (persistable), in
    place."""
    helper = LayerHelper("slot_state_write")
    helper.append_op(type="slot_state_write",
                     inputs={"State": state, "New": new, "Slot": slot},
                     outputs={"StateOut": state}, attrs={})
    return state
