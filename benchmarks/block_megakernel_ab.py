"""A/B: batch-tiled bottleneck MEGAKERNEL vs XLA bottleneck chains.

Three arms per stage, L stacked identity bottlenecks in ONE jitted
self-chained program (marginal protocol, as in conv_kernel_ab.py):

  xla-batchBN : NCHW convs + full-batch train BN — the real model
                semantics the megakernel would replace.
  xla-ghost   : the SAME ghost-BN-per-tile math as the megakernel,
                composed from XLA ops — isolates fusion gain from
                semantics change.
  megakernel  : ops/pallas/block_megakernel.bottleneck_block.

Run on TPU:  python benchmarks/block_megakernel_ab.py [stage2 stage3 stage4]
"""
from __future__ import annotations

import functools
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.block_megakernel import (
    bottleneck_block, bottleneck_block_reference)

EPS = 1e-5
L = 8
N1, N2 = 10, 110


def xla_batch_bn_chain(x, params):
    """L bottlenecks, NCHW, full-batch single-pass train BN."""
    n, cc, h, w_ = x.shape
    m = n * h * w_

    def bn(y, scale, bias, relu=True):
        yf = y.astype(jnp.float32)
        mean = jnp.mean(yf, axis=(0, 2, 3))
        var = jnp.mean(yf * yf, axis=(0, 2, 3)) - mean * mean
        a = (scale * jax.lax.rsqrt(var + EPS)).reshape(1, -1, 1, 1)
        b = (bias - mean * scale * jax.lax.rsqrt(var + EPS)).reshape(
            1, -1, 1, 1)
        out = yf * a + b
        return jnp.maximum(out, 0.0) if relu else out

    def conv(x_, w_m, pad):
        return jax.lax.conv_general_dilated(
            x_, w_m, window_strides=(1, 1), padding=[(pad, pad)] * 2,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    for (w1, w3, w2, bn1, bn2, bn3) in params:
        t = bn(conv(x, w1, 0), bn1[0], bn1[1]).astype(x.dtype)
        t = bn(conv(t, w3, 1), bn2[0], bn2[1]).astype(x.dtype)
        y = bn(conv(t, w2, 0), bn3[0], bn3[1], relu=False)
        x = jnp.maximum(y + x.astype(jnp.float32), 0.0).astype(x.dtype)
    return x


def xla_ghost_chain(x, params, h_img, w_img, tile):
    for (w1, w3, w2, bn1, bn2, bn3) in params:
        x = bottleneck_block_reference(x, w1, w3, w2, bn1, bn2, bn3,
                                       h_img, w_img, tile=tile)
    return x


def mega_chain(x, params, h_img, w_img, tile):
    for (w1, w3, w2, bn1, bn2, bn3) in params:
        x = bottleneck_block(x, w1, w3, w2, bn1, bn2, bn3, h_img,
                             w_img, tile=tile, interpret=False)
    return x


def time_chain(fn, x0, flops_per_call, label):
    from common import time_chain as shared
    return shared(fn, x0, flops_per_call, label, n1=N1, n2=N2)


def run_stage(name, bs, cin, cm, side, rng, tiles=(1, 2, 4)):
    hw = side * side
    print(f"== {name}: bs{bs} {cin}->{cm} @ {side}x{side}, L={L} ==",
          flush=True)

    def mk(shape, fan_in):
        return jnp.asarray(rng.randn(*shape) / np.sqrt(fan_in),
                           jnp.bfloat16)

    flat_params, nchw_params = [], []
    for _ in range(L):
        w1 = mk((cin, cm), cin)
        w3 = mk((9, cm, cm), 9 * cm)
        w2 = mk((cm, cin), cm)
        bns = [jnp.stack([jnp.ones(c), jnp.zeros(c)]).astype(
            jnp.float32) for c in (cm, cm, cin)]
        flat_params.append(tuple([w1, w3, w2] + bns))
        # NCHW OIHW views of the same weights
        w1n = w1.T.reshape(cm, cin, 1, 1)
        w3n = jnp.transpose(
            w3.reshape(3, 3, cm, cm), (3, 2, 0, 1))  # OIHW
        w2n = w2.T.reshape(cin, cm, 1, 1)
        nchw_params.append(tuple([w1n, w3n, w2n] + bns))

    x_flat = jnp.asarray(rng.randn(bs, hw, cin) * 0.5, jnp.bfloat16)
    x_nchw = jnp.asarray(
        np.transpose(np.asarray(x_flat, np.float32).reshape(
            bs, side, side, cin), (0, 3, 1, 2)), jnp.bfloat16)
    flops = L * 2.0 * bs * hw * cm * (cin + 9 * cm + cin)

    time_chain(functools.partial(xla_batch_bn_chain,
                                 params=nchw_params),
               x_nchw, flops, f"{name} XLA batchBN")
    for tile in tiles:
        if bs % tile:
            continue
        time_chain(functools.partial(xla_ghost_chain,
                                     params=flat_params, h_img=side,
                                     w_img=side, tile=tile),
                   x_flat, flops, f"{name} XLA ghost t{tile}")
        try:
            time_chain(functools.partial(mega_chain,
                                         params=flat_params,
                                         h_img=side, w_img=side,
                                         tile=tile),
                       x_flat, flops, f"{name} megakernel t{tile}")
        except Exception as e:
            print(f"{name} megakernel t{tile}: FAILED "
                  f"{repr(e)[:200]}", flush=True)


def main():
    configs = {
        "stage2": (128, 512, 128, 28),
        "stage3": (128, 1024, 256, 14),
        "stage4": (128, 2048, 512, 7),
    }
    which = sys.argv[1:] or ["stage2"]
    rng = np.random.RandomState(0)
    for name in which:
        run_stage(name, *configs[name], rng)


if __name__ == "__main__":
    main()
