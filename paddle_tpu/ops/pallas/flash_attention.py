"""Flash attention as Pallas TPU kernels (forward + backward).

Online-softmax tiled attention: O(S) memory instead of the O(S^2) scores
matrix of the naive composition (reference composes attention from
matmul/softmax in python/paddle/fluid/nets.py:312; its hand-fused CUDA
analogue for recurrent hot loops is paddle/cuda/src/hl_cuda_lstm.cu —
Pallas is the TPU-native equivalent of that hand-fusion layer).

Layout: q [B, H, Sq, D], k [B, H, Sk, D], v [B, H, Sk, Dv] (Dv may differ
from D: latent attention keeps 192-wide keys beside 128-wide values; the
scale stays Q's), out [B, H, Sq, Dv], optional additive bias/mask
broadcastable as [B, {1|H}, Sq, Sk]. The grid iterates
(batch, head, q-block, k-block) with the k-block axis innermost ("arbitrary"
semantics) so VMEM scratch accumulators carry across k-blocks while Mosaic
pipelines the HBM->VMEM block copies.

The backward pass is two more Pallas kernels (dq and dkv) using the
logsumexp residual, plus an exact additive-bias gradient emitted from the
dq kernel — the standard flash-attention-2 recurrence.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


from . import interpret_default as _interpret_default  # shared policy


def _clamp_blocks(sq, sk, block_q, block_k, interpret):
    """Mosaic requires block last-two dims (div 8, div 128) or full-dim.
    Blocks over the scores matrix are (block_q, block_k), so compiled
    kernels need block_q % 8 == 0 and block_k % 128 == 0.

    The requested block size acts as a CAP: the axis is split into the
    fewest blocks that respect it, then the block is shrunk to fit the
    actual length so padding stays under one alignment unit PER BLOCK
    (e.g. sq=1100 with cap 1024 -> 2 blocks of 552 = 1104 padded rows,
    not 2 blocks of 1024 = 2048)."""
    if interpret:
        return min(block_q, _ceil_to(sq, 8)), min(block_k, _ceil_to(sk, 8))
    nq = -(-sq // max(block_q, 8))
    nk = -(-sk // max(block_k, 128))
    return (_ceil_to(-(-sq // nq), 8), _ceil_to(-(-sk // nk), 128))


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, sm_scale, causal, block_q,
                block_k, kv_len):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Whole k-block above the causal diagonal -> nothing to do.
    run = True
    if causal:
        run = iq * block_q + block_q - 1 >= ik * block_k

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]                       # [bq, d]
        k = k_ref[0, 0]                       # [bk, d]
        v = v_ref[0, 0]                       # [bk, dv]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk]
        if bias_ref is not None:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        kpos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)  # mask seq padding
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            s = jnp.where(qpos >= kpos, s, NEG_INF)

        m_prev = m_scr[:, :1]                                 # [bq, 1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                                # [bq, bk]
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bq, dv]
        acc_scr[:] = acc_scr[:] * alpha + pv

    @pl.when(ik == nk - 1)
    def _fin():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)       # fully-masked rows -> 0 out
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-37))
        lse_ref[0, 0] = lse.astype(lse_ref.dtype)


def _bias_spec(bias, sq_p, sk_p, block_q, block_k, order):
    """Padded bias + BlockSpec keeping broadcast (size-1) dims
    unmaterialized: broadcast dims get block size 1 and index 0, and the
    kernel's `s + bias_block` broadcasts in-register. order 'qk' means the
    grid is (b, h, iq, ik); 'kq' is (b, h, ik, iq)."""
    bb, bh, bsq, bsk = bias.shape
    biasp = jnp.pad(bias, ((0, 0), (0, 0),
                           (0, sq_p - bsq if bsq != 1 else 0),
                           (0, sk_p - bsk if bsk != 1 else 0)))
    blk = (1, 1, block_q if bsq != 1 else 1, block_k if bsk != 1 else 1)

    def im_qk(b, h, iq, ik):
        return (0 if bb == 1 else b, 0 if bh == 1 else h,
                0 if bsq == 1 else iq, 0 if bsk == 1 else ik)

    def im_kq(b, h, ik, iq):
        return im_qk(b, h, iq, ik)

    return biasp, pl.BlockSpec(blk, im_qk if order == "qk" else im_kq)


def _fwd(q, k, v, bias, sm_scale, causal, block_q, block_k, interpret):
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    block_q, block_k = _clamp_blocks(sq, sk, block_q, block_k, interpret)
    sq_p, sk_p = _ceil_to(sq, block_q), _ceil_to(sk, block_k)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    grid = (b, h, sq_p // block_q, sk_p // block_k)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_k, d), lambda b, h, iq, ik: (b, h, ik, 0)),
        pl.BlockSpec((1, 1, block_k, dv), lambda b, h, iq, ik: (b, h, ik, 0)),
    ]
    args = [qp, kp, vp]
    if bias is not None:
        biasp, bspec = _bias_spec(bias, sq_p, sk_p, block_q, block_k, "qk")
        in_specs.append(bspec)
        args.append(biasp)

        kernel = functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
            block_k=block_k, kv_len=sk)
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m, l, a):
            return _fwd_kernel(q_ref, k_ref, v_ref, None, o_ref, lse_ref,
                               m, l, a, sm_scale=sm_scale, causal=causal,
                               block_q=block_q, block_k=block_k, kv_len=sk)

    scratch = [
        _scratch((block_q, 128), jnp.float32),
        _scratch((block_q, 128), jnp.float32),
        _scratch((block_q, dv), jnp.float32),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, h, sq_p, dv), q.dtype),
        jax.ShapeDtypeStruct((b, h, sq_p, 128), jnp.float32),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, block_q, dv), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_q, 128),
                     lambda b, h, iq, ik: (b, h, iq, 0)),
    ]
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(("parallel",) * 3 + ("arbitrary",)),
        interpret=interpret,
    )(*args)
    return o[:, :, :sq], lse[:, :, :sq, :1]   # lse kept [B,H,Sq,1]


def _scratch(shape, dtype):
    return pltpu.VMEM(shape, dtype)


def _compiler_params(dimension_semantics):
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dbias_ref, dq_scr, *, sm_scale, causal, block_q,
               block_k, kv_len):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True
    if causal:
        run = iq * block_q + block_q - 1 >= ik * block_k

    @pl.when(run)
    def _step():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        do = do_ref[0, 0]                                    # [bq, dv]
        lse = lse_ref[0, 0][:, :1]                           # [bq, 1]
        delta = delta_ref[0, 0][:, :1]                       # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if bias_ref is not None:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse)                                 # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bq, bk]
        ds = p * (dp - delta)                                # [bq, bk]
        if dbias_ref is not None:
            dbias_ref[0, 0] = ds.astype(dbias_ref.dtype)
        dq_scr[:] += sm_scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if dbias_ref is not None:
        @pl.when(jnp.logical_not(run))
        def _zero_bias():
            dbias_ref[0, 0] = jnp.zeros_like(dbias_ref[0, 0])

    @pl.when(ik == nk - 1)
    def _fin():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal,
                block_q, block_k, kv_len):
    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        run = iq * block_q + block_q - 1 >= ik * block_k

    @pl.when(run)
    def _step():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if bias_ref is not None:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse)                                 # [bq, bk]
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bk, dv]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[:] += sm_scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bk, d]

    @pl.when(iq == nq - 1)
    def _fin():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(res, g, sm_scale, causal, block_q, block_k, interpret,
         bias_needs_grad):
    q, k, v, bias, o, lse = res
    do = g
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    block_q, block_k = _clamp_blocks(sq, sk, block_q, block_k, interpret)
    sq_p, sk_p = _ceil_to(sq, block_q), _ceil_to(sk, block_k)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                 # [B,H,Sq,1]
    pad_q = ((0, 0), (0, 0), (0, sq_p - sq), (0, 0))
    pad_k = ((0, 0), (0, 0), (0, sk_p - sk), (0, 0))
    qp, dop = jnp.pad(q, pad_q), jnp.pad(do, pad_q)
    kp, vp = jnp.pad(k, pad_k), jnp.pad(v, pad_k)
    # lse rows for padded q positions must not produce NaN in exp(s - lse):
    lsep = jnp.pad(jnp.broadcast_to(lse, (b, h, sq, 128)), pad_q)
    deltap = jnp.pad(jnp.broadcast_to(delta, (b, h, sq, 128)), pad_q)

    def qspec(im, width=d):
        return pl.BlockSpec((1, 1, block_q, width), im)

    def kspec(im, width=d):
        return pl.BlockSpec((1, 1, block_k, width), im)

    def rspec(im):  # row stats [.., 128]
        return pl.BlockSpec((1, 1, block_q, 128), im)

    # ---- dq (+ dbias) over grid (b, h, iq, ik), k innermost ----
    qk_q = lambda b, h, iq, ik: (b, h, iq, 0)
    qk_k = lambda b, h, iq, ik: (b, h, ik, 0)
    in_specs = [qspec(qk_q), kspec(qk_k), kspec(qk_k, dv)]
    args = [qp, kp, vp]
    has_bias = bias is not None
    if has_bias:
        biasp, bspec = _bias_spec(bias, sq_p, sk_p, block_q, block_k, "qk")
        in_specs.append(bspec)
        args.append(biasp)
    in_specs += [qspec(qk_q, dv), rspec(qk_q), rspec(qk_q)]
    args += [dop, lsep, deltap]

    out_shape = [jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype)]
    out_specs = [qspec(qk_q)]
    emit_dbias = has_bias and bias_needs_grad
    if emit_dbias:
        out_shape.append(jax.ShapeDtypeStruct(
            (b, h, sq_p, sk_p), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (1, 1, block_q, block_k), lambda b, h, iq, ik: (b, h, iq, ik)))

    def dq_kernel(*refs):
        n_in = len(args)
        ins, outs, scr = refs[:n_in], refs[n_in:-1], refs[-1]
        bias_ref = ins[3] if has_bias else None
        rest = ins[3 + int(has_bias):]
        _dq_kernel(ins[0], ins[1], ins[2], bias_ref, rest[0], rest[1],
                   rest[2], outs[0],
                   outs[1] if emit_dbias else None, scr,
                   sm_scale=sm_scale, causal=causal, block_q=block_q,
                   block_k=block_k, kv_len=sk)

    res_dq = pl.pallas_call(
        dq_kernel,
        name="flash_bwd_dq",
        grid=(b, h, sq_p // block_q, sk_p // block_k),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[_scratch((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(("parallel",) * 3 + ("arbitrary",)),
        interpret=interpret,
    )(*args)
    if emit_dbias:
        dq, dbias_full = res_dq
        dbias_full = dbias_full[:, :, :sq, :sk]
        # reduce over every broadcast dim of the original bias
        for ax in range(4):
            if bias.shape[ax] == 1 and dbias_full.shape[ax] != 1:
                dbias_full = jnp.sum(dbias_full, axis=ax, keepdims=True)
        dbias = dbias_full.astype(bias.dtype)
    else:
        dq = res_dq[0]
        dbias = jnp.zeros_like(bias) if bias is not None else None
    dq = dq[:, :, :sq]

    # ---- dk/dv over grid (b, h, ik, iq), q innermost ----
    kq_q = lambda b, h, ik, iq: (b, h, iq, 0)
    kq_k = lambda b, h, ik, iq: (b, h, ik, 0)
    in_specs = [qspec(kq_q), kspec(kq_k), kspec(kq_k, dv)]
    args2 = [qp, kp, vp]
    if has_bias:
        biasp, bspec = _bias_spec(bias, sq_p, sk_p, block_q, block_k, "kq")
        in_specs.append(bspec)
        args2.append(biasp)
    in_specs += [qspec(kq_q, dv), rspec(kq_q), rspec(kq_q)]
    args2 += [dop, lsep, deltap]

    def dkv_kernel(*refs):
        n_in = len(args2)
        ins, outs, scr = refs[:n_in], refs[n_in:n_in + 2], refs[n_in + 2:]
        bias_ref = ins[3] if has_bias else None
        rest = ins[3 + int(has_bias):]
        _dkv_kernel(ins[0], ins[1], ins[2], bias_ref, rest[0], rest[1],
                    rest[2], outs[0], outs[1], scr[0], scr[1],
                    sm_scale=sm_scale, causal=causal, block_q=block_q,
                    block_k=block_k, kv_len=sk)

    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_bwd_dkv",
        grid=(b, h, sk_p // block_k, sq_p // block_q),
        in_specs=in_specs,
        out_specs=[kspec(kq_k), kspec(kq_k, dv)],
        out_shape=[jax.ShapeDtypeStruct((b, h, sk_p, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, sk_p, dv), v.dtype)],
        scratch_shapes=[_scratch((block_k, d), jnp.float32),
                        _scratch((block_k, dv), jnp.float32)],
        compiler_params=_compiler_params(("parallel",) * 3 + ("arbitrary",)),
        interpret=interpret,
    )(*args2)
    dk, dv = dk[:, :, :sk], dv[:, :, :sk]
    return dq, dk, dv, dbias


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, bias, sm_scale, causal, block_q, block_k, interpret,
           bias_grad):
    o, _ = _fwd(q, k, v, bias, sm_scale, causal, block_q, block_k,
                interpret)
    return o


def _flash_fwd(q, k, v, bias, sm_scale, causal, block_q, block_k,
               interpret, bias_grad):
    o, lse = _fwd(q, k, v, bias, sm_scale, causal, block_q, block_k,
                  interpret)
    return o, (q, k, v, bias, o, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, interpret, bias_grad,
               res, g):
    dq, dk, dv, dbias = _bwd(res, g, sm_scale, causal, block_q, block_k,
                             interpret, bias_needs_grad=bias_grad)
    return dq, dk, dv, dbias


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, bias: Optional[jax.Array] = None,
                    causal: bool = False, sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    bias_grad: bool = False) -> jax.Array:
    """Tiled online-softmax attention.

    q: [B, H, Sq, D]; k: [B, H, Sk, D]; v: [B, H, Sk, Dv]; bias additive
    with any of the four dims broadcast (size 1). Returns [B, H, Sq, Dv].
    sm_scale defaults to 1/sqrt(D), Q's width, whatever Dv is.

    bias_grad=False (default) treats bias as a constant mask: backward
    returns zeros for it without materializing the O(Sq*Sk) dbias buffer.
    Set bias_grad=True for trainable biases (e.g. relative-position bias);
    the gradient is then emitted from the dq kernel and summed over any
    broadcast dims.

    block_q/block_k act as CAPS on the tile size: the sequence is split
    into the fewest cap-respecting tiles and the tile shrinks to fit
    (minimizing padding), so an explicit 256 with sq=900 runs 4 tiles
    of 232. None selects the per-path default cap below, swept on v5e
    with stacked-layer fwd+bwd marginal timing: 1024x1024 beat 128x128
    by 1.4x at seq 256, 2.7x at 1024, and was still fastest at 4096.
    """
    if interpret is None:
        interpret = _interpret_default()
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    # Default tile caps (explicit block_q/block_k always win): 1024 for
    # bias-free attention; a materialized bias adds score-sized blocks
    # to every kernel's VMEM footprint, so mask-bias defaults to 512
    # (~5 score-sized fp32 buffers = 5MB, well under the 16MB
    # scoped-vmem limit). Trainable-bias grads additionally accumulate
    # dbias tiles and show larger fp32 reassociation drift at big tiles
    # (~4e-3 rel between 128 and 512 at S=1024 on v5e) — they default
    # to the original 128 tiling for bit-stable gradients.
    if bias is None:
        default_blk = 1024
    elif bias_grad:
        default_blk = 128
    else:
        default_blk = 512
    block_q = default_blk if block_q is None else block_q
    block_k = default_blk if block_k is None else block_k
    if bias is not None:
        if bias.ndim == 2:        # [Sq|1, Sk|1]
            bias = bias[None, None]
        elif bias.ndim == 3:      # [B|1, Sq|1, Sk|1]
            bias = bias[:, None]
    return _flash(q, k, v, bias, float(sm_scale), bool(causal),
                  int(block_q), int(block_k), bool(interpret),
                  bool(bias_grad))
