"""Fused GRU time loop as Pallas TPU kernels (forward + backward).

Companion to fused_lstm.py (the reference hand-fuses GRU the same way in
paddle/cuda — hl_cuda_lstm.cu's sibling kernels). Recurrent state h
stays in VMEM scratch across all timesteps; backward walks in reverse
recomputing gates from (x_t, h_prev).

Layout (matches ops/sequence_ops.py _gru):
  x  [T, B, 3H]  pre-projected (+bias folded in by the caller),
                 order u (update), r (reset), c (candidate)
  w  [H, 3H]     packs [H, 2H] update/reset + [H, H] candidate
  h0 [B, H]; lengths [B] ragged mask (frozen rows / zeroed outputs,
  identical to _masked_scan_rnn).
  h = u * h_prev + (1 - u) * tanh(xc + (r * h_prev) @ w_c)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from . import interpret_default as _interpret_default  # shared policy


def _gates(x_t, h_prev, w_ref, hidden):
    w = w_ref[...].astype(jnp.float32)
    w_ur = w[:, :2 * hidden]
    w_c = w[:, 2 * hidden:]
    ur = jax.lax.dot_general(h_prev, w_ur, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    u = jax.nn.sigmoid(x_t[:, :hidden] + ur[:, :hidden])
    r = jax.nn.sigmoid(x_t[:, hidden:2 * hidden] + ur[:, hidden:])
    rh = r * h_prev
    c = jnp.tanh(x_t[:, 2 * hidden:] +
                 jax.lax.dot_general(rh, w_c, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32))
    return u, r, rh, c, w_ur, w_c


def _fwd_kernel(len_ref, x_ref, w_ref, h0_ref, h_all_ref, h_scr, *,
                hidden):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    h_prev = h_scr[...]
    x_t = x_ref[0].astype(jnp.float32)
    u, r, rh, c, _, _ = _gates(x_t, h_prev, w_ref, hidden)
    h_new = u * h_prev + (1.0 - u) * c

    alive = t < len_ref[...]                     # [B, 1]
    h_scr[...] = jnp.where(alive, h_new, h_prev)
    h_all_ref[0] = jnp.where(alive, h_new,
                             jnp.zeros_like(h_new)).astype(h_all_ref.dtype)


def _bwd_kernel(len_ref, x_ref, w_ref, h0_ref, h_all_ref, dh_out_ref,
                dx_ref, dw_ref, dh0_ref,
                dh_scr, dw_scr, *, hidden, t_max):
    k = pl.program_id(0)
    t = t_max - 1 - k

    @pl.when(k == 0)
    def _init():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        dw_scr[...] = jnp.zeros_like(dw_scr)

    use_init = (t == 0)
    h_prev = jnp.where(use_init, h0_ref[...].astype(jnp.float32),
                       h_all_ref[0].astype(jnp.float32))
    x_t = x_ref[0].astype(jnp.float32)
    u, r, rh, c, w_ur, w_c = _gates(x_t, h_prev, w_ref, hidden)

    alive = t < len_ref[...]
    dh = dh_out_ref[0].astype(jnp.float32) + dh_scr[...]
    dh = jnp.where(alive, dh, jnp.zeros_like(dh))

    du_pre = dh * (h_prev - c) * u * (1.0 - u)
    dc_pre = dh * (1.0 - u) * (1.0 - c * c)
    d_rh = jax.lax.dot_general(dc_pre, w_c, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    dr_pre = d_rh * h_prev * r * (1.0 - r)
    dur_pre = jnp.concatenate([du_pre, dr_pre], axis=1)

    dh_prev = dh * u + d_rh * r + jax.lax.dot_general(
        dur_pre, w_ur, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    dx = jnp.concatenate([du_pre, dr_pre, dc_pre], axis=1)
    dx_ref[0] = jnp.where(alive, dx, jnp.zeros_like(dx)
                          ).astype(dx_ref.dtype)
    # dead rows contribute zeros automatically: every pre-activation
    # grad is proportional to the masked dh
    dw_ur = jax.lax.dot_general(h_prev, dur_pre,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    dw_c = jax.lax.dot_general(rh, dc_pre, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    dw_scr[...] += jnp.concatenate([dw_ur, dw_c], axis=1)

    dh_scr[...] = jnp.where(alive, dh_prev, dh_scr[...])

    @pl.when(k == t_max - 1)
    def _final():
        dw_ref[...] = dw_scr[...].astype(dw_ref.dtype)
        dh0_ref[...] = dh_scr[...].astype(dh0_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_gru(x, w, h0, lengths, interpret=None):
    """[T, B, 3H] pre-projected -> (h_all [T, B, H], h_last [B, H])."""
    return _fused_gru_fwd(x, w, h0, lengths, interpret)[0]


def _run_fwd(x, w, h0, lengths, interpret):
    if interpret is None:
        interpret = _interpret_default()
    t_max, bsz, g3 = x.shape
    hidden = g3 // 3
    kernel = functools.partial(_fwd_kernel, hidden=hidden)
    h_all = pl.pallas_call(
        kernel,
        name="fused_gru_fwd",
        grid=(t_max,),
        in_specs=[
            pl.BlockSpec((bsz, 1), lambda t: (0, 0)),
            pl.BlockSpec((1, bsz, g3), lambda t: (t, 0, 0)),
            pl.BlockSpec((hidden, g3), lambda t: (0, 0)),
            pl.BlockSpec((bsz, hidden), lambda t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bsz, hidden), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t_max, bsz, hidden), x.dtype),
        scratch_shapes=[pltpu.VMEM((bsz, hidden), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(lengths.astype(jnp.int32).reshape(bsz, 1), x, w, h0)
    lens32 = lengths.astype(jnp.int32)
    idx = jnp.maximum(lens32 - 1, 0)
    h_last = jnp.take_along_axis(
        jnp.moveaxis(h_all, 0, 1), idx[:, None, None], axis=1)[:, 0]
    h_last = jnp.where((lens32 == 0)[:, None], h0.astype(h_last.dtype),
                       h_last)
    return h_all, h_last


def _fused_gru_fwd(x, w, h0, lengths, interpret):
    h_all, h_last = _run_fwd(x, w, h0, lengths, interpret)
    return (h_all, h_last), (x, w, h0, lengths, h_all)


def _fused_gru_bwd(interpret, res, grads):
    x, w, h0, lengths, h_all = res
    dh_all, dh_last = grads
    if interpret is None:
        interpret = _interpret_default()
    t_max, bsz, g3 = x.shape
    hidden = g3 // 3
    lens32 = lengths.astype(jnp.int32)
    idx = jnp.maximum(lens32 - 1, 0)
    dh_all = jnp.moveaxis(jnp.moveaxis(dh_all, 0, 1).at[
        jnp.arange(bsz), idx].add(
            jnp.where((lens32 == 0)[:, None], 0.0, dh_last)), 1, 0)

    kernel = functools.partial(_bwd_kernel, hidden=hidden, t_max=t_max)
    dx, dw, dh0 = pl.pallas_call(
        kernel,
        name="fused_gru_bwd",
        grid=(t_max,),
        in_specs=[
            pl.BlockSpec((bsz, 1), lambda k: (0, 0)),
            pl.BlockSpec((1, bsz, g3), lambda k: (t_max - 1 - k, 0, 0)),
            pl.BlockSpec((hidden, g3), lambda k: (0, 0)),
            pl.BlockSpec((bsz, hidden), lambda k: (0, 0)),
            pl.BlockSpec((1, bsz, hidden),
                         lambda k: (jnp.maximum(t_max - 2 - k, 0), 0, 0)),
            pl.BlockSpec((1, bsz, hidden),
                         lambda k: (t_max - 1 - k, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bsz, g3), lambda k: (t_max - 1 - k, 0, 0)),
            pl.BlockSpec((hidden, g3), lambda k: (0, 0)),
            pl.BlockSpec((bsz, hidden), lambda k: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_max, bsz, g3), x.dtype),
            jax.ShapeDtypeStruct((hidden, g3), w.dtype),
            jax.ShapeDtypeStruct((bsz, hidden), h0.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bsz, hidden), jnp.float32),
                        pltpu.VMEM((hidden, g3), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(lens32.reshape(bsz, 1), x, w, h0, h_all, dh_all)
    # grad of the zero-length h_last passthrough
    dh0 = dh0 + jnp.where((lens32 == 0)[:, None], dh_last, 0.0)
    return dx, dw, dh0, None


fused_gru.defvjp(_fused_gru_fwd, _fused_gru_bwd)
