"""Batch-tiled cross-layer bottleneck megakernel (round-4 campaign).

The round-3 roofline analysis showed the ResNet-50
train step pinned to the HBM roofline at ~40 GB/step vs a ~16 GB hand
ideal: every conv boundary writes its activation to HBM and the next
conv reads it back. Whole-block fusion was ruled out there because a
STAGE-wide activation (51-205 MB) cannot sit in VMEM — but that sizing
assumed whole-batch tiles. This kernel grids over the BATCH instead:
a tile of `tile` images' activations for one bottleneck block
(1x1 -> BN/relu -> 3x3 -> BN/relu -> 1x1 -> BN -> +residual -> relu)
lives entirely in VMEM (~10 MB at stage-2 shapes, tile=2), the block's
weights stay VMEM-resident across the sequential grid (constant-index
blocks are not refetched), and the only HBM traffic is x in, y out —
the hand-ideal byte count.

Spatial structure inside the flat [tile*H*W, C] layout: the 3x3 is
nine shifted matmuls; a tap (dy,dx) is a whole-array row rotation by
dy*W+dx (pltpu.roll on the f32 activation — Mosaic's rotate needs
32-bit data, the same constraint fused_conv.py hit) masked by the
per-pixel validity of (h+dy, w+dx). Rows that rotate across an image
boundary are exactly the rows the validity mask zeroes, so no halo
DMA and no pixel-pair geometry — the two things that made round 3's
spatially-tiled 3x3 ~5x slower than XLA's conv.

BatchNorm inside a batch tile is GHOST BN: statistics over the tile's
`tile*H*W` samples rather than the full batch (the standard ghost-BN
regularizer, here with ghost size = tile images). This is what makes
cross-layer fusion possible at all — full-batch stats would need a
cross-program barrier between every conv. Training-semantics parity is
a measured question (tests/test_block_megakernel.py convergence test),
not assumed.

Reference anchor: the hand-fusion precedent paddle/cuda/src/
hl_cuda_lstm.cu (reference optimizes ITS hot path with hand-fused
kernels; this is the TPU-shaped analog for the conv hot path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_default

EPS = 1e-5


def _ghost_coefs(h, p_ref, eps):
    """(a, b) [1, C] f32 from ghost stats of f32 [M, C]."""
    m = h.shape[0]
    mean = jnp.sum(h, axis=0, keepdims=True) / m
    var = jnp.sum(h * h, axis=0, keepdims=True) / m - mean * mean
    a = p_ref[0:1, :] * jax.lax.rsqrt(var + eps)
    return a, p_ref[1:2, :] - mean * a


def _bottleneck_kernel(x_ref, w1_ref, w3_ref, w2_ref, p1_ref, p2_ref,
                       p3_ref, out_ref, *, h_img, w_img, tile, eps):
    """VPU-lean variant (the first cut measured VPU-bound at 39% MXU,
    ~parity with XLA): BN1's affine+relu fuses into the tap masking
    pass (affine is per-lane, so it commutes with row rotation), the
    nine taps collapse into three K=3*Cm dots (one per dy), and the
    validity masks are built once from a single iota."""
    hw = h_img * w_img
    m = tile * hw
    x = x_ref[:]                                        # bf16 [M, Cin]
    cm = w1_ref.shape[1]
    dt = x_ref.dtype

    acc1 = jnp.dot(x, w1_ref[:], preferred_element_type=jnp.float32)
    a1, b1 = _ghost_coefs(acc1, p1_ref, eps)            # [1, Cm]
    a1t = jnp.concatenate([a1, a1, a1], axis=1)         # [1, 3Cm]
    b1t = jnp.concatenate([b1, b1, b1], axis=1)

    row = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    p_local = row % hw
    hh = p_local // w_img
    ww = p_local % w_img
    w_ok = [ww - 1 >= 0, row >= 0, ww + 1 < w_img]      # dx = -1, 0, 1

    # w3_ref is tap-major [9, Cm, Cm], t = (dy+1)*3 + (dx+1); a dy-trio
    # reshapes to the [3Cm, Cm] right operand of one MXU dot
    acc2 = jnp.zeros((m, cm), jnp.float32)
    for dy in (-1, 0, 1):
        base = pltpu.roll(acc1, (-dy * w_img) % m, 0) if dy else acc1
        h_ok = (hh + dy >= 0) & (hh + dy < h_img)
        trio = jnp.concatenate(
            [base if dx == 0 else pltpu.roll(base, (-dx) % m, 0)
             for dx in (-1, 0, 1)], axis=1)             # [M, 3Cm]
        mask = jnp.concatenate(
            [jnp.broadcast_to(h_ok & wk, (m, cm)) for wk in w_ok],
            axis=1)
        # fused: BN1 affine + relu + boundary mask + bf16 cast
        tap = jnp.where(mask,
                        jnp.maximum(trio * a1t + b1t, 0.0), 0.0)
        wt = w3_ref[(dy + 1) * 3:(dy + 1) * 3 + 3].reshape(3 * cm, cm)
        acc2 = acc2 + jnp.dot(tap.astype(dt), wt,
                              preferred_element_type=jnp.float32)

    a2, b2 = _ghost_coefs(acc2, p2_ref, eps)
    h2 = jnp.maximum(acc2 * a2 + b2, 0.0).astype(dt)    # one fused pass

    acc3 = jnp.dot(h2, w2_ref[:], preferred_element_type=jnp.float32)
    a3, b3 = _ghost_coefs(acc3, p3_ref, eps)
    y = acc3 * a3 + b3 + x.astype(jnp.float32)
    out_ref[:] = jnp.maximum(y, 0.0).astype(out_ref.dtype)


def bottleneck_block(x, w1, w3, w2, bn1, bn2, bn3, h_img, w_img,
                     tile=2, eps=EPS, interpret=None):
    """Fused identity bottleneck block forward, ghost-BN training stats.

    x: [N, H*W, Cin] NHWC-flat bf16 (or f32 in interpret tests).
    w1 [Cin, Cm], w3 [9, Cm, Cm] (tap-major: t = (dy+1)*3 + dx+1),
    w2 [Cm, Cin]; bn1/bn2/bn3: [2, C] f32 rows (gamma, beta).
    Returns y [N, H*W, Cin] in x.dtype.
    """
    if interpret is None:
        interpret = interpret_default()
    n, hw, cin = x.shape
    assert hw == h_img * w_img, (hw, h_img, w_img)
    cm = w1.shape[1]
    assert n % tile == 0, (n, tile)
    assert cin % 128 == 0 and cm % 128 == 0, \
        "stage-1 (Cm=64) needs lane packing — not built; see fused_conv"
    m = tile * hw
    xf = x.reshape(n * hw, cin)
    kern = functools.partial(_bottleneck_kernel, h_img=h_img,
                             w_img=w_img, tile=tile, eps=eps)
    flops = 2 * n * hw * cm * (cin + 9 * cm + cin)
    out = pl.pallas_call(
        kern,
        name="block_megakernel",
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((m, cin), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((cin, cm), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((9, cm, cm), lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((cm, cin), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((2, cm), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((2, cm), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((2, cin), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((m, cin), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n * hw, cin), x.dtype),
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=2 * x.size * x.dtype.itemsize,
            transcendentals=0),
        interpret=interpret,
    )(xf, w1, w3, w2,
      jnp.asarray(bn1, jnp.float32), jnp.asarray(bn2, jnp.float32),
      jnp.asarray(bn3, jnp.float32))
    return out.reshape(n, hw, cin)


def bottleneck_block_reference(x, w1, w3, w2, bn1, bn2, bn3, h_img,
                               w_img, tile=2, eps=EPS):
    """jnp oracle with IDENTICAL ghost-BN semantics (stats per
    tile-of-images group), for correctness tests and as the XLA-side
    arm of the same-semantics perf A/B."""
    n, hw, cin = x.shape
    cm = w1.shape[1]

    def ghost_bn(h, p, relu):
        # h [G, M, C] f32, stats over axis 1 within each group
        mean = h.mean(axis=1, keepdims=True)
        var = (h * h).mean(axis=1, keepdims=True) - mean * mean
        a = p[0] * jax.lax.rsqrt(var + eps)
        b = p[1] - mean * a
        y = h * a + b
        return jnp.maximum(y, 0.0) if relu else y

    g = n // tile
    xg = x.reshape(g, tile * hw, cin)
    h1 = ghost_bn(jnp.einsum("gmk,kn->gmn", xg, w1,
                             preferred_element_type=jnp.float32),
                  jnp.asarray(bn1, jnp.float32), True)
    img = h1.reshape(g * tile, h_img, w_img, cm)
    padded = jnp.pad(img, ((0, 0), (1, 1), (1, 1), (0, 0)))
    acc = jnp.zeros((g * tile, h_img, w_img, cm), jnp.float32)
    for t in range(9):
        dy, dx = t // 3, t % 3
        tap = padded[:, dy:dy + h_img, dx:dx + w_img, :]
        acc = acc + jnp.einsum(
            "bhwk,kn->bhwn", tap.astype(x.dtype), w3[t],
            preferred_element_type=jnp.float32)
    h2 = ghost_bn(acc.reshape(g, tile * hw, cm),
                  jnp.asarray(bn2, jnp.float32), True)
    y = ghost_bn(jnp.einsum("gmk,kn->gmn", h2.astype(x.dtype), w2,
                            preferred_element_type=jnp.float32),
                 jnp.asarray(bn3, jnp.float32), False)
    y = y + xg.astype(jnp.float32)
    return jnp.maximum(y, 0.0).astype(x.dtype).reshape(n, hw, cin)
