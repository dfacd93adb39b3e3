"""Pallas fused conv+BN kernels for the ResNet hot path.

The reference answers conv+BN cost with vendor-fused kernels
(reference: paddle/fluid/operators/conv_cudnn_op.cu.cc:1); the TPU-native
answer is Pallas kernels that fold BatchNorm's activation sweeps into the
convolutions that already touch the data:

- the conv kernel's EPILOGUE accumulates per-channel sum / sum-of-squares
  of its raw f32 accumulator output (BN statistics for free — the XLA
  path re-reads the conv output from HBM for them);
- the NEXT conv kernel's PROLOGUE applies the producer BN's per-channel
  affine (y = x*a + b) and ReLU while the input tile is in VMEM (the XLA
  path materializes the normalized activation as its own HBM pass).

Net effect: each activation buffer is written once (raw conv output) and
read once (next conv's input) — BN costs no extra HBM sweeps. Internal
layout is NHWC-flat ([N*H*W, C] row-major), the MXU-native shape for a
1x1 conv (a plain matmul) and for 3x3 as nine shifted matmuls.

All kernels run under interpret mode on CPU for tests (see
tests/test_fused_conv.py) and compiled on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_default


def _pick_block_m(m: int, vmem_budget_rows: int = 1024) -> int:
    """Largest divisor of m that is a multiple of 16 (bf16 sublane tile)
    and <= the row budget."""
    for cand in range(min(vmem_budget_rows, m), 15, -16):
        if m % cand == 0:
            return cand
    return m  # last resort: single block (m itself)


def _conv1x1_kernel(x_ref, w_ref, a_ref, b_ref, out_ref, stats_ref,
                    *, relu, stats, affine, out_dtype):
    """One [BM, K] x [K, N] tile: optional input affine+relu prologue,
    matmul, optional stats epilogue accumulated across the M grid."""
    x = x_ref[:]
    if affine:
        xf = x.astype(jnp.float32) * a_ref[:] + b_ref[:]
        if relu:
            xf = jnp.maximum(xf, 0.0)
        x = xf.astype(x_ref.dtype)
    elif relu:
        x = jnp.maximum(x, 0)
    out = jnp.dot(x, w_ref[:], preferred_element_type=jnp.float32)
    out_ref[:] = out.astype(out_dtype)
    if stats:
        @pl.when(pl.program_id(0) == 0)
        def _init():
            stats_ref[:] = jnp.zeros_like(stats_ref)
        stats_ref[0, :] += jnp.sum(out, axis=0)
        stats_ref[1, :] += jnp.sum(out * out, axis=0)


def conv1x1_bn_act(x, w, a=None, b=None, relu=False, stats=True,
                   block_m=None, interpret=None):
    """Fused pointwise conv on NHWC-flat input.

    x: [M, K] (M = N*H*W rows, K input channels), any float dtype.
    w: [K, N] weights.
    a, b: optional per-input-channel affine coefficients [K] f32 — the
        PRODUCER BatchNorm's normalize (a = scale*rsqrt(var+eps),
        b = bias - mean*a), applied (then ReLU if relu=True) to x in
        the prologue.
    Returns (out [M, N] in x.dtype, stats [2, N] f32) where stats rows
    are (sum, sum_of_squares) of the f32 conv output over M — exactly
    what the CONSUMER BatchNorm needs; stats is None if stats=False.
    """
    if interpret is None:
        interpret = interpret_default()
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    affine = a is not None
    if affine:
        a = jnp.asarray(a, jnp.float32).reshape(1, k)
        b = jnp.asarray(b, jnp.float32).reshape(1, k)
    else:
        # dummy tiny operands keep the kernel signature static
        a = jnp.zeros((1, 1), jnp.float32)
        b = jnp.zeros((1, 1), jnp.float32)
    bm = block_m or _pick_block_m(m)
    grid = (m // bm,)
    kernel = functools.partial(
        _conv1x1_kernel, relu=relu, stats=stats, affine=affine,
        out_dtype=x.dtype)
    out_shapes = [jax.ShapeDtypeStruct((m, n), x.dtype),
                  jax.ShapeDtypeStruct((2, n), jnp.float32)]
    out_specs = [
        pl.BlockSpec((bm, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((2, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
    ]
    out, stats_out = pl.pallas_call(
        kernel,
        name="fused_conv1x1",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, n), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(a.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(b.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n,
            bytes_accessed=(m * k + m * n) * x.dtype.itemsize + k * n * 4,
            transcendentals=0),
    )(x, w, a, b)
    return out, (stats_out if stats else None)


def _conv3x3_kernel(x_hbm, w_ref, a_ref, b_ref, out_ref, stats_ref,
                    slab, im2col, sem, *, relu, stats, affine,
                    out_dtype, bm, c, img_w, img_h, m_total):
    """3x3 stride-1 pad-1 conv on NHWC-flat rows as ONE im2col matmul
    per tile: a halo slab (bm + 2*(W+1) rows) is DMA'd from HBM, the
    producer-BN affine(+relu) is applied once to the slab, nine shifted
    views (masked at image edges) form the [bm, 9C] im2col tile in
    VMEM, and a single [bm, 9C] x [9C, N] dot hits the MXU with a deep
    contraction even for narrow C."""
    i = pl.program_id(0)
    halo = -(-(img_w + 1) // 8) * 8   # 8-aligned: DMA offsets/sizes
    slab_rows = bm + 2 * halo         # must sit on sublane tiles

    # three DMA shapes (static sizes): interior, first, last tile
    nm = pl.num_programs(0)

    # Boundary rows that fall outside x are never READ un-masked (the
    # h/w validity masks below zero every out-of-image tap), so the
    # boundary tiles only need their copies clamped, not zero-filled.
    # pl.multiple_of: Mosaic must PROVE dynamic DMA row offsets sit on
    # sublane tiles (bm and halo are both multiples of 8).
    @pl.when(jnp.logical_and(i > 0, i < nm - 1))
    def _interior():
        cp = pltpu.make_async_copy(
            x_hbm.at[pl.ds(pl.multiple_of(i * bm - halo, 8),
                           slab_rows)], slab, sem)
        cp.start()
        cp.wait()

    @pl.when(i == 0)
    def _first():
        # slab[halo + j] = x[j]; rows [0, halo) stay garbage (masked)
        cp = pltpu.make_async_copy(
            x_hbm.at[pl.ds(0, bm + halo)],
            slab.at[pl.ds(halo, bm + halo)], sem)
        cp.start()
        cp.wait()

    @pl.when(jnp.logical_and(i == nm - 1, nm > 1))
    def _last():
        # tail rows past x's end stay garbage (masked)
        cp = pltpu.make_async_copy(
            x_hbm.at[pl.ds(pl.multiple_of(i * bm - halo, 8),
                           bm + halo)],
            slab.at[pl.ds(0, bm + halo)], sem)
        cp.start()
        cp.wait()

    # f32 through the rolls (Mosaic's rotate needs 32-bit data); the
    # im2col store downcasts back to the input dtype for the MXU
    sl = slab[:].astype(jnp.float32)
    if affine:
        sl = sl * a_ref[:] + b_ref[:]
    if relu:
        sl = jnp.maximum(sl, 0.0)

    # row coordinates of the bm output rows
    r = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0) + i * bm
    h = (r // img_w) % img_h
    w_pos = r % img_w

    for t, (dh, dw) in enumerate((dh, dw) for dh in (-1, 0, 1)
                                 for dw in (-1, 0, 1)):
        off = halo + dh * img_w + dw          # static, in [0, 2*halo]
        # Mosaic cannot slice VMEM at unaligned sublane offsets; a
        # static roll + aligned [0:bm] slice expresses the same shift
        rows = sl.shape[0]
        tap = pltpu.roll(sl, rows - off, 0)[0:bm]
        valid = (h + dh >= 0) & (h + dh < img_h) & \
                (w_pos + dw >= 0) & (w_pos + dw < img_w)
        im2col[:, t * c:(t + 1) * c] = jnp.where(valid, tap, 0.0).astype(
            im2col.dtype)
    out = jnp.dot(im2col[:], w_ref[:], preferred_element_type=jnp.float32)
    out_ref[:] = out.astype(out_dtype)
    if stats:
        @pl.when(i == 0)
        def _init():
            stats_ref[:] = jnp.zeros_like(stats_ref)
        stats_ref[0, :] += jnp.sum(out, axis=0)
        stats_ref[1, :] += jnp.sum(out * out, axis=0)


def _pack_paired_w(w_flat, c, n):
    """Re-express tap-major 3x3 weights [9c, n] for the pixel-PAIR
    geometry: two adjacent pixels fold into one 2c-lane row (Mosaic
    DMAs need >=128 lanes), so the conv becomes 9 pair-taps with a
    [9*2c, 2n] weight carrying structural zeros (dw = 2*dp +
    half_in - half_out must land in {-1,0,1})."""
    wp = jnp.zeros((9 * 2 * c, 2 * n), w_flat.dtype)
    for dh in (-1, 0, 1):
        for dp in (-1, 0, 1):
            tp = (dh + 1) * 3 + (dp + 1)
            for half_in in (0, 1):
                for half_out in (0, 1):
                    dw = 2 * dp + half_in - half_out
                    if dw < -1 or dw > 1:
                        continue
                    t = (dh + 1) * 3 + (dw + 1)
                    wp = wp.at[
                        tp * 2 * c + half_in * c:
                        tp * 2 * c + half_in * c + c,
                        half_out * n: half_out * n + n,
                    ].set(w_flat[t * c:(t + 1) * c, :])
    return wp


def conv3x3_bn_act(x, w, img_h, img_w, a=None, b=None, relu=False,
                   stats=True, block_m=None, interpret=None):
    """Fused 3x3 stride-1 pad-1 conv on NHWC-flat input.

    x: [M, C] with M = N*img_h*img_w rows in NHWC-flat order.
    w: [9*C, N] tap-major weights (tap t = (dh+1)*3 + (dw+1) occupies
        rows t*C : (t+1)*C) — `pack_w3x3` converts OIHW.
    a, b, relu, stats: as conv1x1_bn_act (producer-BN prologue on x,
        consumer-BN stats epilogue on the f32 output).

    C must be a multiple of 128 (Mosaic lane tiling), or exactly 64 —
    the 64-channel case (ResNet stage 1) runs in a pixel-pair geometry:
    x reshapes (free) to [M/2, 128] rows of two adjacent pixels, the
    weights gain structural zeros (2x MXU work on an HBM-bound shape),
    and the output/stats fold back — wrapper-level only, same kernel.
    """
    if interpret is None:
        interpret = interpret_default()
    m, c = x.shape
    k9, n = w.shape
    assert k9 == 9 * c, (x.shape, w.shape)
    assert m % (img_h * img_w) == 0, (m, img_h, img_w)
    if c == 64 and img_w % 2 == 0:
        out, st = conv3x3_bn_act(
            x.reshape(m // 2, 2 * c), _pack_paired_w(w, c, n),
            img_h, img_w // 2,
            a=None if a is None else jnp.concatenate([a, a]),
            b=None if b is None else jnp.concatenate([b, b]),
            relu=relu, stats=stats,
            block_m=None,   # geometry halved: re-pick a valid divisor
            interpret=interpret)
        out = out.reshape(m, n)
        if st is not None:
            st = st[:, :n] + st[:, n:]
        return out, st
    affine = a is not None
    if affine:
        a = jnp.asarray(a, jnp.float32).reshape(1, c)
        b = jnp.asarray(b, jnp.float32).reshape(1, c)
    else:
        a = jnp.zeros((1, 1), jnp.float32)
        b = jnp.zeros((1, 1), jnp.float32)
    halo = -(-(img_w + 1) // 8) * 8
    bm = block_m or _pick_block_m(m, 512)
    assert m % bm == 0, (m, bm)
    if bm < halo + 8 or m // bm < 2 or \
            (not interpret and c % 128 != 0):
        # tiny inputs: one whole-array tile would need special DMA
        # cases; not the hot path — compose from the 1x1 kernel's
        # building blocks at the JAX level instead
        return _conv3x3_small(x, w, img_h, img_w, a if affine else None,
                              b if affine else None, relu, stats,
                              interpret)
    grid = (m // bm,)
    kernel = functools.partial(
        _conv3x3_kernel, relu=relu, stats=stats, affine=affine,
        out_dtype=x.dtype, bm=bm, c=c, img_w=img_w, img_h=img_h,
        m_total=m)
    out, stats_out = pl.pallas_call(
        kernel,
        name="fused_conv3x3",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.ANY),       # x stays in HBM
            pl.BlockSpec((k9, n), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(a.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(b.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((2, n), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct((m, n), x.dtype),
                   jax.ShapeDtypeStruct((2, n), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((bm + 2 * halo, c), x.dtype),
            pltpu.VMEM((bm, 9 * c), x.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * m * 9 * c * n,
            bytes_accessed=(m * c + m * n) * x.dtype.itemsize
            + k9 * n * 4,
            transcendentals=0),
    )(x, w, a, b)
    return out, (stats_out if stats else None)


def _conv3x3_small(x, w, img_h, img_w, a, b, relu, stats, interpret):
    """Fallback for shapes too small for the halo kernel: same math in
    plain jnp (XLA) — shifted adds on the flat layout."""
    m, c = x.shape
    xf = x.astype(jnp.float32)
    if a is not None:
        xf = xf * a + b
        if relu:
            xf = jnp.maximum(xf, 0.0)
        xf = xf.astype(x.dtype).astype(jnp.float32)
    elif relu:
        xf = jnp.maximum(xf, 0.0)
    imgs = xf.reshape(-1, img_h, img_w, c)
    cols = []
    for dh in (-1, 0, 1):
        for dw in (-1, 0, 1):
            sh = jnp.roll(imgs, (-dh, -dw), axis=(1, 2))
            hi = jnp.arange(img_h)[None, :, None, None]
            wi = jnp.arange(img_w)[None, None, :, None]
            valid = (hi + dh >= 0) & (hi + dh < img_h) & \
                    (wi + dw >= 0) & (wi + dw < img_w)
            cols.append(jnp.where(valid, sh, 0.0))
    im2col = jnp.concatenate(cols, axis=-1).reshape(m, 9 * c)
    out = jnp.dot(im2col.astype(x.dtype), w,
                  preferred_element_type=jnp.float32)
    st = jnp.stack([out.sum(0), (out * out).sum(0)]) if stats else None
    return out.astype(x.dtype), st


def pack_w3x3(w_oihw):
    """[O, I, 3, 3] -> tap-major [9*I, O] for conv3x3_bn_act."""
    o, i, kh, kw = w_oihw.shape
    assert kh == 3 and kw == 3
    # tap-major: [kh, kw, I, O]
    return jnp.transpose(w_oihw, (2, 3, 1, 0)).reshape(9 * i, o)


def reference_conv1x1_bn_act(x, w, a=None, b=None, relu=False):
    """Pure-jnp oracle for tests: same math, composed ops."""
    xf = x.astype(jnp.float32)
    if a is not None:
        xf = xf * jnp.asarray(a, jnp.float32)[None, :] \
            + jnp.asarray(b, jnp.float32)[None, :]
        if relu:
            xf = jnp.maximum(xf, 0.0)
        xf = xf.astype(x.dtype).astype(jnp.float32)
    elif relu:
        xf = jnp.maximum(xf, 0.0)
    out = jnp.dot(xf.astype(x.dtype), w,
                  preferred_element_type=jnp.float32)
    stats = jnp.stack([out.sum(0), (out * out).sum(0)])
    return out.astype(x.dtype), stats
