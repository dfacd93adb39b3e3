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

The backward pass is ONE more Pallas kernel using the logsumexp
residual — the flash-attention-2 recurrence with each score tile's s, p,
dp and ds computed once and feeding dV, dK and dQ (a dq and a dkv kernel
would each recompute them). Its grid is (batch, head, k-segment, q-block):
a head's K and V, with f32 dK and dV accumulators beside them, stay
resident in VMEM while the q-blocks stream, an in-kernel loop walks the
k-blocks (up to the diagonal on a causal site), and dQ leaves finished.
How much of K and V stays resident is a byte count against _VMEM_BUDGET:
where a whole head does not fit, the same kernel takes them a segment at
a time and dQ leaves as an f32 partial a segment for XLA to sum. An
exact additive-bias gradient is emitted from the same tiles on request.
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


def _compiler_params(dimension_semantics, **more):
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                **more)


# ---------------------------------------------------------------------------
# backward kernel
# ---------------------------------------------------------------------------

# What one backward call may plan to keep in VMEM: half of a v5e core's
# 128 MiB, the rest being Mosaic's own. The call asks for what it
# counted (vmem_limit_bytes), not for the default scoped 16 MiB.
_VMEM_BUDGET = 64 << 20


def _bwd_blocks(sq, sk, block_q, block_k, interpret):
    """The backward's tiles under the caps: each axis split into the
    fewest tiles that respect its cap, the tile shrunk to fit. A score
    tile is held keys-down [block_k, block_q] and K, V, dK, dV are
    sliced by rows of block_k, so compiled tiles are whole 128-lane
    columns one way and whole packed sublane tiles the other."""
    unit = 8 if interpret else 128

    def fit(s, cap):
        n = -(-s // max(cap, unit))
        return _ceil_to(-(-s // n), unit)

    return fit(sq, block_q), fit(sk, block_k)


def _bwd_vmem_bytes(chunks, block_q, block_k, d, dv, itemsize, bias_lanes,
                    emit_dbias):
    """Bytes a backward call keeps in VMEM with `chunks` k-blocks of one
    head resident: K, V and the dK, dV blocks double-buffered by the
    pipeline, their f32 accumulators, the bias (and dbias) rows beside
    them; one q-block's operands and dQ; the f32 score-sized values of
    a tile in flight."""
    per_key = (4 * itemsize + 4) * (d + dv) + 8 * bias_lanes
    if emit_dbias:
        per_key += 8 * block_q
    per_step = block_q * (2 * itemsize * (d + dv) + 12 * d + 256)
    return (chunks * block_k * per_key + per_step
            + 6 * 4 * block_q * block_k)


def _bwd_kernel(*refs, sm_scale, causal, block_q, block_k, kv_len, chunks,
                bias_kind, emit_dbias):
    """One q-block against the `chunks` k-blocks of one resident
    k-segment, scores held keys-down ([block_k, block_q]: the row
    statistics are rows, and only dQ's product contracts over a
    transposed operand). Each tile's s, p, dp, ds are computed once and
    feed dV, dK and dQ."""
    n_in = 6 + (bias_kind is not None)
    q_ref, k_ref, v_ref = refs[:3]
    bias_ref = refs[3] if bias_kind is not None else None
    do_ref, lse_ref, delta_ref = refs[n_in - 3:n_in]
    dq_ref, dk_ref, dv_ref = refs[n_in:n_in + 3]
    dbias_ref = refs[n_in + 3] if emit_dbias else None
    dq_scr, dk_scr, dv_scr = refs[-3:]
    ks, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    dq_scr[:] = jnp.zeros_like(dq_scr)
    if emit_dbias:        # the tiles that do not run still own a block
        dbias_ref[0, 0] = jnp.zeros_like(dbias_ref[0, 0])

    q, do = q_ref[0, 0], do_ref[0, 0]          # [bq, d], [bq, dv]
    lse, delta = lse_ref[0, 0, 0], delta_ref[0, 0, 0]       # [1, bq]
    first = ks * chunks
    # k-blocks past the last key, or wholly above the causal diagonal:
    # nothing to do
    stop = jnp.minimum(chunks, -(-kv_len // block_k) - first)
    if causal:
        stop = jnp.minimum(
            stop, (iq * block_q + block_q - 1) // block_k + 1 - first)

    def _tile(c, carry):
        rows = pl.ds(pl.multiple_of(c * block_k, block_k), block_k)
        k, v = k_ref[0, 0, rows, :], v_ref[0, 0, rows, :]
        s = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bk, bq]
        if bias_kind == "key":      # one value a key, on every lane
            s = s + bias_ref[0, 0, rows, :][:, :1].astype(jnp.float32)
        elif bias_kind == "score":
            s = s + bias_ref[0, 0, rows, :].astype(jnp.float32)
        kpos = (first + c) * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        if kv_len % block_k:        # mask seq padding
            s = jnp.where(kpos < kv_len, s, NEG_INF)
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse)                                 # [bk, bq]
        dv_scr[rows, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bk, dv]
        dp = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bk, bq]
        ds = p * (dp - delta)
        if emit_dbias:
            dbias_ref[0, 0, rows, :] = ds
        ds = ds.astype(q.dtype)
        dk_scr[rows, :] += sm_scale * jax.lax.dot_general(
            ds, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bk, d]
        dq_scr[:] += sm_scale * jax.lax.dot_general(
            ds, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bq, d]
        return carry

    jax.lax.fori_loop(0, stop, _tile, None)
    dq_ref[0, 0, 0] = dq_scr[:].astype(dq_ref.dtype)

    @pl.when(iq == nq - 1)
    def _fin():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _count_bwd_site(path):
    from ...observability.registry import default_registry
    default_registry().counter(
        "paddle_tpu_flash_bwd_sites_total",
        "flash-attention backward calls traced, by what the call's byte "
        "count against the VMEM budget let it keep resident (resident: "
        "a head's whole K and V, so dK, dV and dQ leave the kernel "
        "finished; partial: K and V a segment at a time, dQ written "
        "once a segment in f32 and summed by XLA).",
        ("path",)).labels(path=path).inc()


def _bwd(res, g, sm_scale, causal, block_q, block_k, interpret,
         bias_needs_grad):
    q, k, v, bias, o, lse = res
    do = g
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    bias_kind = None if bias is None else (
        "key" if bias.shape[2] == 1 else "score")
    emit_dbias = bias is not None and bias_needs_grad
    # Tile caps the caller left open: a score-sized bias or dbias block
    # rides with every tile, so those keep the forward's caps; a
    # key-row mask is one column of a tile and caps nothing. Half of a
    # tile on the causal diagonal is masked work, so a causal site
    # takes the smaller tile (v5e, PR 38: 1.81 against 1.99 ms a site
    # of 64 heads x 2048 x 64; not causal 2.50 against 2.42).
    if emit_dbias:
        cap = 128
    elif bias_kind == "score" or causal:
        cap = 512
    else:
        cap = 1024
    block_q, block_k = _bwd_blocks(
        sq, sk, cap if block_q is None else block_q,
        cap if block_k is None else block_k, interpret)
    nq, nk = -(-sq // block_q), -(-sk // block_k)
    # How much of a head's K and V stays resident is a byte count: all
    # of it where that fits, else the fewest equal segments that do.
    bias_lanes = {None: 0, "key": 128, "score": block_q}[bias_kind]

    def vmem_bytes(chunks):
        return _bwd_vmem_bytes(chunks, block_q, block_k, d, dv,
                               q.dtype.itemsize, bias_lanes, emit_dbias)

    chunks = nk
    while chunks > 1 and vmem_bytes(chunks) > _VMEM_BUDGET:
        chunks -= 1
    nseg = -(-nk // chunks)
    chunks = -(-nk // nseg)
    seg = chunks * block_k
    sq_p, sk_p = nq * block_q, nseg * seg
    _count_bwd_site("resident" if nseg == 1 else "partial")

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                # [B,H,Sq]
    pad_q = ((0, 0), (0, 0), (0, sq_p - sq), (0, 0))
    pad_k = ((0, 0), (0, 0), (0, sk_p - sk), (0, 0))
    qp, dop = jnp.pad(q, pad_q), jnp.pad(do, pad_q)
    kp, vp = jnp.pad(k, pad_k), jnp.pad(v, pad_k)

    def rows(x):        # a row a q-block; pads read lse 0: exp stays finite
        return jnp.pad(x, pad_q[:3]).reshape(b, h, nq, 1, block_q)

    def qspec(width):
        return pl.BlockSpec((1, 1, block_q, width),
                            lambda b, h, ks, iq: (b, h, iq, 0))

    def kspec(width):
        return pl.BlockSpec((1, 1, seg, width),
                            lambda b, h, ks, iq: (b, h, ks, 0))

    rspec = pl.BlockSpec((1, 1, 1, 1, block_q),
                         lambda b, h, ks, iq: (b, h, iq, 0, 0))
    in_specs = [qspec(d), kspec(d), kspec(dv)]
    args = [qp, kp, vp]
    if bias is not None:
        bb, bh = bias.shape[:2]
        if bias_kind == "key":      # [.., 1, Sk|1] -> [.., sk_p, 128]
            biasp = jnp.broadcast_to(
                jnp.pad(jnp.broadcast_to(bias[:, :, 0], (bb, bh, sk)),
                        pad_k[:3])[..., None], (bb, bh, sk_p, 128))
        else:                       # [.., Sq, Sk|1] -> [.., sk_p, sq_p]
            biasp = jnp.pad(
                jnp.swapaxes(jnp.broadcast_to(bias, (bb, bh, sq, sk)), 2, 3),
                ((0, 0), (0, 0), (0, sk_p - sk), (0, sq_p - sq)))
        in_specs.append(pl.BlockSpec(
            (1, 1, seg, bias_lanes),
            lambda b, h, ks, iq: (0 if bb == 1 else b, 0 if bh == 1 else h,
                                  ks, iq if bias_kind == "score" else 0)))
        args.append(biasp)
    in_specs += [qspec(dv), rspec, rspec]
    args += [dop, rows(lse[..., 0]), rows(delta)]

    # dQ a k-segment: finished where there is one, else an f32 partial
    out_shape = [
        jax.ShapeDtypeStruct((nseg, b, h, sq_p, d),
                             q.dtype if nseg == 1 else jnp.float32),
        jax.ShapeDtypeStruct((b, h, sk_p, d), k.dtype),
        jax.ShapeDtypeStruct((b, h, sk_p, dv), v.dtype)]
    out_specs = [
        pl.BlockSpec((1, 1, 1, block_q, d),
                     lambda b, h, ks, iq: (ks, b, h, iq, 0)),
        kspec(d), kspec(dv)]
    if emit_dbias:
        out_shape.append(jax.ShapeDtypeStruct(
            (b, h, sk_p, sq_p), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (1, 1, seg, block_q), lambda b, h, ks, iq: (b, h, ks, iq)))

    outs = pl.pallas_call(
        functools.partial(
            _bwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
            block_k=block_k, kv_len=sk, chunks=chunks, bias_kind=bias_kind,
            emit_dbias=emit_dbias),
        # the benchmark's readers find the backward by flash_bwd_(dq|dkv)
        name="flash_bwd_dkv_dq",
        grid=(b, h, nseg, nq),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[_scratch((block_q, d), jnp.float32),
                        _scratch((seg, d), jnp.float32),
                        _scratch((seg, dv), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel",) * 3 + ("arbitrary",),
            vmem_limit_bytes=max(32 << 20, 5 * vmem_bytes(chunks) // 4)),
        interpret=interpret,
    )(*args)
    dq, dk, dv_ = outs[:3]
    dq = dq[0] if nseg == 1 else jnp.sum(dq, axis=0).astype(q.dtype)
    if emit_dbias:
        dbias = jnp.swapaxes(outs[3], 2, 3)[:, :, :sq, :sk]
        # reduce over every broadcast dim of the original bias
        for ax in range(4):
            if bias.shape[ax] == 1 and dbias.shape[ax] != 1:
                dbias = jnp.sum(dbias, axis=ax, keepdims=True)
        dbias = dbias.astype(bias.dtype)
    else:
        dbias = jnp.zeros_like(bias) if bias is not None else None
    return dq[:, :, :sq], dk[:, :, :sk], dv_[:, :, :sk], dbias


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def _fwd_caps(bias, bias_grad, block_q, block_k):
    """The forward's tile caps (explicit block_q/block_k always win):
    1024 for bias-free attention; a bias hands every tile a block of
    its own, score-sized where it has a query axis, so mask-bias
    defaults to 512 (~5 score-sized fp32 buffers = 5MB, well under the
    16MB scoped-vmem limit). Trainable-bias grads show larger fp32
    reassociation drift at big tiles (~4e-3 rel between 128 and 512 at
    S=1024 on v5e) — they default to the original 128 tiling for
    bit-stable gradients."""
    if bias is None:
        default_blk = 1024
    elif bias_grad:
        default_blk = 128
    else:
        default_blk = 512
    return (default_blk if block_q is None else block_q,
            default_blk if block_k is None else block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, bias, sm_scale, causal, block_q, block_k, interpret,
           bias_grad):
    o, _ = _fwd(q, k, v, bias, sm_scale, causal,
                *_fwd_caps(bias, bias_grad, block_q, block_k), interpret)
    return o


def _flash_fwd(q, k, v, bias, sm_scale, causal, block_q, block_k,
               interpret, bias_grad):
    o, lse = _fwd(q, k, v, bias, sm_scale, causal,
                  *_fwd_caps(bias, bias_grad, block_q, block_k), interpret)
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
    the gradient is then emitted from the backward kernel a score tile at
    a time and summed over any broadcast dims.

    block_q/block_k act as CAPS on the tile size: the sequence is split
    into the fewest cap-respecting tiles and the tile shrinks to fit
    (minimizing padding), so an explicit 256 with sq=900 runs 4 tiles
    of 232 forward (of 256 backward, whose compiled tiles are whole
    128-lane columns). None lets each pass choose from what it is
    handed: the forward by _fwd_caps, swept on v5e with stacked-layer
    fwd+bwd marginal timing (1024x1024 beat 128x128 by 1.4x at seq 256,
    2.7x at 1024, and was still fastest at 4096); the backward by _bwd.
    """
    if interpret is None:
        interpret = _interpret_default()
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if bias is not None:
        if bias.ndim == 2:        # [Sq|1, Sk|1]
            bias = bias[None, None]
        elif bias.ndim == 3:      # [B|1, Sq|1, Sk|1]
            bias = bias[:, None]
    return _flash(q, k, v, bias, float(sm_scale), bool(causal),
                  None if block_q is None else int(block_q),
                  None if block_k is None else int(block_k),
                  bool(interpret), bool(bias_grad))
