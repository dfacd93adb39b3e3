"""Fused LSTM time loop as Pallas TPU kernels (forward + backward).

The reference hand-fuses its LSTM hot loop in CUDA
(paddle/cuda/src/hl_cuda_lstm.cu; used by lstm_op's batched compute).
This is the TPU-native equivalent: one kernel runs ALL timesteps with the
recurrent state (h, c) resident in VMEM scratch and the recurrent weight
streamed once, so the per-step HBM traffic is just x_t in / h_t out —
instead of a lax.scan whose every step round-trips state through HBM.

Layout (matches ops/sequence_ops.py _lstm):
  x   [T, B, 4H]  pre-projected gates, time-major; gate order i,c_hat,f,o
  w   [H, 4H]     recurrent weights
  b   [4H]        gate bias (already includes any projection bias)
  h0, c0 [B, H]
  lengths [B]     ragged mask: rows freeze past their length and masked
                  outputs are zero, identical to _masked_scan_rnn.

Backward is a second kernel walking t in reverse, recomputing gate
activations from (x_t, h_{t-1}) — flash-style recompute, so only h_all
and c_all are saved, not the [T, B, 4H] gates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from . import interpret_default as _interpret_default  # shared policy


def _fwd_kernel(len_ref, x_ref, w_ref, b_ref, h0_ref, c0_ref,
                h_all_ref, c_all_ref, h_scr, c_scr, *, hidden):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)
        c_scr[...] = c0_ref[...].astype(jnp.float32)

    h_prev = h_scr[...]
    c_prev = c_scr[...]
    gates = x_ref[0].astype(jnp.float32) + \
        jax.lax.dot_general(h_prev, w_ref[...].astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) + \
        b_ref[...].astype(jnp.float32)              # b: [1, 4H]
    i = jax.nn.sigmoid(gates[:, :hidden])
    cand = jnp.tanh(gates[:, hidden:2 * hidden])
    f = jax.nn.sigmoid(gates[:, 2 * hidden:3 * hidden])
    o = jax.nn.sigmoid(gates[:, 3 * hidden:])
    c_new = f * c_prev + i * cand
    h_new = o * jnp.tanh(c_new)

    alive = t < len_ref[...]                     # [B, 1]
    c_scr[...] = jnp.where(alive, c_new, c_prev)
    h_scr[...] = jnp.where(alive, h_new, h_prev)
    zeros = jnp.zeros_like(h_new)
    h_all_ref[0] = jnp.where(alive, h_new, zeros).astype(h_all_ref.dtype)
    c_all_ref[0] = jnp.where(alive, c_new, zeros).astype(c_all_ref.dtype)


def _bwd_kernel(len_ref, x_ref, w_ref, b_ref, h0_ref, c0_ref,
                h_all_ref, c_all_ref, dh_out_ref, dc_out_ref,
                dx_ref, dw_ref, db_ref, dh0_ref, dc0_ref,
                dh_scr, dc_scr, *, hidden, t_max):
    # dw/db accumulate IN their fp32 output buffers (constant block
    # mapping + sequential grid) instead of separate VMEM scratch — the
    # extra [H, 4H] scratch copy pushed large shapes over the 16MB
    # scoped-vmem limit (b64 h512 t64 in an 8-layer stack).
    k = pl.program_id(0)
    t = t_max - 1 - k

    @pl.when(k == 0)
    def _init():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        dc_scr[...] = jnp.zeros_like(dc_scr)
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    # previous-step state: h_all/c_all blocks are indexed at t-1 via the
    # BlockSpec (clamped at 0); substitute h0/c0 when t == 0
    use_init = (t == 0)
    h_prev = jnp.where(use_init, h0_ref[...].astype(jnp.float32),
                       h_all_ref[0].astype(jnp.float32))
    c_prev = jnp.where(use_init, c0_ref[...].astype(jnp.float32),
                       c_all_ref[0].astype(jnp.float32))

    gates = x_ref[0].astype(jnp.float32) + \
        jax.lax.dot_general(h_prev, w_ref[...].astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) + \
        b_ref[...].astype(jnp.float32)
    i = jax.nn.sigmoid(gates[:, :hidden])
    cand = jnp.tanh(gates[:, hidden:2 * hidden])
    f = jax.nn.sigmoid(gates[:, 2 * hidden:3 * hidden])
    o = jax.nn.sigmoid(gates[:, 3 * hidden:])
    c = f * c_prev + i * cand
    tc = jnp.tanh(c)

    alive = t < len_ref[...]                     # [B, 1]
    dh = dh_out_ref[0].astype(jnp.float32) + dh_scr[...]
    dh = jnp.where(alive, dh, jnp.zeros_like(dh))
    dc = dh * o * (1.0 - tc * tc) + dc_scr[...] + \
        dc_out_ref[0].astype(jnp.float32)
    dc = jnp.where(alive, dc, dc_scr[...])

    do_pre = jnp.where(alive, dh * tc * o * (1.0 - o), 0.0)
    df_pre = jnp.where(alive, dc * c_prev * f * (1.0 - f), 0.0)
    di_pre = jnp.where(alive, dc * cand * i * (1.0 - i), 0.0)
    dch_pre = jnp.where(alive, dc * i * (1.0 - cand * cand), 0.0)
    dgates = jnp.concatenate([di_pre, dch_pre, df_pre, do_pre], axis=1)

    dx_ref[0] = dgates.astype(dx_ref.dtype)
    dw_ref[...] += jax.lax.dot_general(
        h_prev, dgates, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    db_ref[...] += jnp.sum(dgates, axis=0, keepdims=True)

    dh_prev = jax.lax.dot_general(
        dgates, w_ref[...].astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    # frozen rows pass their carries through untouched
    dh_scr[...] = jnp.where(alive, dh_prev, dh_scr[...])
    dc_scr[...] = jnp.where(alive, dc * f, dc_scr[...])

    @pl.when(k == t_max - 1)
    def _final():
        dh0_ref[...] = dh_scr[...].astype(dh0_ref.dtype)
        dc0_ref[...] = dc_scr[...].astype(dc0_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def fused_lstm(x, w, b, h0, c0, lengths, interpret=None):
    """[T, B, 4H] pre-projected gates -> (h_all [T, B, H], c_all,
    h_last [B, H], c_last)."""
    out = _fused_lstm_fwd(x, w, b, h0, c0, lengths, interpret)
    return out[0]


def _run_fwd(x, w, b, h0, c0, lengths, interpret):
    if interpret is None:
        interpret = _interpret_default()
    t_max, bsz, g4 = x.shape
    hidden = g4 // 4
    kernel = functools.partial(_fwd_kernel, hidden=hidden)
    h_all, c_all = pl.pallas_call(
        kernel,
        name="fused_lstm_fwd",
        grid=(t_max,),
        in_specs=[
            pl.BlockSpec((bsz, 1), lambda t: (0, 0)),          # lengths
            pl.BlockSpec((1, bsz, g4), lambda t: (t, 0, 0)),   # x_t
            pl.BlockSpec((hidden, g4), lambda t: (0, 0)),      # w
            pl.BlockSpec((1, g4), lambda t: (0, 0)),           # b
            pl.BlockSpec((bsz, hidden), lambda t: (0, 0)),     # h0
            pl.BlockSpec((bsz, hidden), lambda t: (0, 0)),     # c0
        ],
        out_specs=[
            pl.BlockSpec((1, bsz, hidden), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, bsz, hidden), lambda t: (t, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_max, bsz, hidden), x.dtype),
            jax.ShapeDtypeStruct((t_max, bsz, hidden), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bsz, hidden), jnp.float32),
                        pltpu.VMEM((bsz, hidden), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(lengths.astype(jnp.int32).reshape(bsz, 1), x, w,
      b.reshape(1, g4), h0, c0)
    # last valid state per row; zero-length rows keep their initial
    # state (scan-path semantics)
    lens32 = lengths.astype(jnp.int32)
    idx = jnp.maximum(lens32 - 1, 0)
    h_last = jnp.take_along_axis(
        jnp.moveaxis(h_all, 0, 1), idx[:, None, None], axis=1)[:, 0]
    c_last = jnp.take_along_axis(
        jnp.moveaxis(c_all, 0, 1), idx[:, None, None], axis=1)[:, 0]
    empty = (lens32 == 0)[:, None]
    h_last = jnp.where(empty, h0.astype(h_last.dtype), h_last)
    c_last = jnp.where(empty, c0.astype(c_last.dtype), c_last)
    return (h_all, c_all, h_last, c_last)


def _fused_lstm_fwd(x, w, b, h0, c0, lengths, interpret):
    outs = _run_fwd(x, w, b, h0, c0, lengths, interpret)
    h_all, c_all, _, _ = outs
    return outs, (x, w, b, h0, c0, lengths, h_all, c_all)


def _fused_lstm_bwd(interpret, res, grads):
    x, w, b, h0, c0, lengths, h_all, c_all = res
    dh_all, dc_all, dh_last, dc_last = grads
    if interpret is None:
        interpret = _interpret_default()
    t_max, bsz, g4 = x.shape
    hidden = g4 // 4
    # fold the h_last/c_last cotangents back into the per-step streams
    idx = jnp.maximum(lengths.astype(jnp.int32) - 1, 0)
    dh_all = jnp.moveaxis(jnp.moveaxis(dh_all, 0, 1).at[
        jnp.arange(bsz), idx].add(dh_last), 1, 0)
    dc_all = jnp.moveaxis(jnp.moveaxis(dc_all, 0, 1).at[
        jnp.arange(bsz), idx].add(dc_last), 1, 0)

    kernel = functools.partial(_bwd_kernel, hidden=hidden, t_max=t_max)
    dx, dw, db, dh0, dc0 = pl.pallas_call(
        kernel,
        name="fused_lstm_bwd",
        grid=(t_max,),
        in_specs=[
            pl.BlockSpec((bsz, 1), lambda k: (0, 0)),
            pl.BlockSpec((1, bsz, g4), lambda k: (t_max - 1 - k, 0, 0)),
            pl.BlockSpec((hidden, g4), lambda k: (0, 0)),
            pl.BlockSpec((1, g4), lambda k: (0, 0)),
            pl.BlockSpec((bsz, hidden), lambda k: (0, 0)),
            pl.BlockSpec((bsz, hidden), lambda k: (0, 0)),
            # h_all/c_all indexed at t-1 (clamped to 0; t==0 substitutes
            # h0/c0 inside the kernel)
            pl.BlockSpec((1, bsz, hidden),
                         lambda k: (jnp.maximum(t_max - 2 - k, 0), 0, 0)),
            pl.BlockSpec((1, bsz, hidden),
                         lambda k: (jnp.maximum(t_max - 2 - k, 0), 0, 0)),
            pl.BlockSpec((1, bsz, hidden),
                         lambda k: (t_max - 1 - k, 0, 0)),
            pl.BlockSpec((1, bsz, hidden),
                         lambda k: (t_max - 1 - k, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bsz, g4), lambda k: (t_max - 1 - k, 0, 0)),
            pl.BlockSpec((hidden, g4), lambda k: (0, 0)),
            pl.BlockSpec((1, g4), lambda k: (0, 0)),
            pl.BlockSpec((bsz, hidden), lambda k: (0, 0)),
            pl.BlockSpec((bsz, hidden), lambda k: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_max, bsz, g4), x.dtype),
            # fp32 accumulators (cast to param dtype after the call) —
            # accumulating 4H-wide sums in bf16 would lose precision
            jax.ShapeDtypeStruct((hidden, g4), jnp.float32),
            jax.ShapeDtypeStruct((1, g4), jnp.float32),
            jax.ShapeDtypeStruct((bsz, hidden), h0.dtype),
            jax.ShapeDtypeStruct((bsz, hidden), c0.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bsz, hidden), jnp.float32),
                        pltpu.VMEM((bsz, hidden), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(lengths.astype(jnp.int32).reshape(bsz, 1), x, w,
      b.reshape(1, g4), h0, c0, h_all, c_all, dh_all, dc_all)
    return dx, dw.astype(w.dtype), db.reshape(g4).astype(b.dtype), \
        dh0, dc0, None


fused_lstm.defvjp(_fused_lstm_fwd, _fused_lstm_bwd)
