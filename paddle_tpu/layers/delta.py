"""Layer wrappers of the gated delta-rule ops (ops/delta_ops.py). The
parameters and the persistable state are the caller's: a served model
creates them once and hands them to the prefill and the decode program
alike (models/delta_hybrid.py)."""
from __future__ import annotations

from ..layer_helper import LayerHelper

__all__ = ["gated_delta_prefill", "gated_delta_state_update"]


def _attrs(beta_scale, norm_eps):
    return {"beta_scale": float(beta_scale), "norm_eps": float(norm_eps)}


def gated_delta_prefill(q, k, v, a, b, a_log, dt_bias, length, chunk=64,
                        beta_scale=2.0, norm_eps=1e-6, initial=None):
    """q, k [n, S, H * d_k], v [n, S, H * d_v], a, b [n, S, H], length
    [n] -> (out [n, S, H * d_v], the state after each row's last real
    position [n, d_k, H * d_v] float32). ``initial`` is the state the
    rows start from (zeros where None)."""
    helper = LayerHelper("gated_delta_prefill")
    out = helper.create_tmp_variable(v.dtype)
    state = helper.create_tmp_variable("float32")
    inputs = {"Q": q, "K": k, "V": v, "A": a, "B": b, "ALog": a_log,
              "DtBias": dt_bias, "Length": length}
    if initial is not None:
        inputs["Initial"] = initial
    helper.append_op(
        type="gated_delta_prefill", inputs=inputs,
        outputs={"Out": out, "State": state},
        attrs=dict(_attrs(beta_scale, norm_eps), chunk=int(chunk)))
    return out, state


def gated_delta_state_update(state, q, k, v, a, b, a_log, dt_bias,
                             beta_scale=2.0, norm_eps=1e-6):
    """One token a slot; ``state`` (persistable) is updated in place."""
    helper = LayerHelper("gated_delta_state_update")
    out = helper.create_tmp_variable(v.dtype)
    helper.append_op(
        type="gated_delta_state_update",
        inputs={"State": state, "Q": q, "K": k, "V": v, "A": a, "B": b,
                "ALog": a_log, "DtBias": dt_bias},
        outputs={"Out": out, "StateOut": state},
        attrs=_attrs(beta_scale, norm_eps))
    return out
