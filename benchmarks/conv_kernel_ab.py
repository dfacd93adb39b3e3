"""A/B: XLA conv1x1+BN+relu chain vs Pallas fused conv+BN kernel.

Measures L stacked layers in ONE jitted program (stacking makes compute
dwarf dispatch), chained across calls via buffer donation,
marginal-cost timed (t(n2)-t(n1)).

Run on TPU:  python benchmarks/conv_kernel_ab.py [stage]
"""
from __future__ import annotations

import functools
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.fused_conv import (conv1x1_bn_act,
                                              conv3x3_bn_act, pack_w3x3)

EPS = 1e-5
L = 16
N1, N2 = 10, 110   # ~100-call marginal delta


def xla_chain(x, ws, scales, biases):
    """L layers of conv1x1 (NCHW) -> train-mode BN (single-pass stats +
    coefficient normalize, the ops/nn_ops.py _bn_train math) -> relu."""
    n, c, h, w_ = x.shape
    m = n * h * w_
    for wmat, scale, bias in zip(ws, scales, biases):
        y = jax.lax.conv_general_dilated(
            x, wmat, window_strides=(1, 1), padding=[(0, 0), (0, 0)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        yf = y.astype(jnp.float32)
        s1 = jnp.sum(yf, axis=(0, 2, 3))
        s2 = jnp.sum(yf * yf, axis=(0, 2, 3))
        mean = s1 / m
        var = s2 / m - mean * mean
        inv = jax.lax.rsqrt(var + EPS)
        a = (scale * inv).reshape(1, -1, 1, 1)
        b = (bias - mean * scale * inv).reshape(1, -1, 1, 1)
        x = jnp.maximum(yf * a + b, 0.0).astype(y.dtype)
    return x


def pallas_chain(x, ws, scales, biases):
    """Same math, fused: conv kernel epilogue yields stats; the next
    kernel's prologue applies the BN affine + relu."""
    m = x.shape[0]
    a = b = None
    for wmat, scale, bias in zip(ws, scales, biases):
        out, st = conv1x1_bn_act(x, wmat, a, b, relu=a is not None,
                                 stats=True, interpret=False)
        mean = st[0] / m
        var = st[1] / m - mean * mean
        inv = jax.lax.rsqrt(var + EPS)
        a = scale * inv
        b = bias - mean * a
        x = out
    return jnp.maximum(x.astype(jnp.float32) * a[None, :] + b[None, :],
                       0.0).astype(x.dtype)


def _renorm(x):
    """Keep the self-chained activations in range across calls, so the
    chain never collapses to all zeros."""
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf) + 1e-6)).astype(x.dtype)


def conv_only_xla(x, ws):
    for wmat in ws:
        x = jax.lax.conv_general_dilated(
            x, wmat, window_strides=(1, 1), padding=[(0, 0), (0, 0)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return _renorm(x)


def conv_only_pallas(x, ws):
    for wmat in ws:
        x, _ = conv1x1_bn_act(x, wmat, stats=False, interpret=False)
    return _renorm(x)


def _bn_coefs(st, m, scale, bias):
    mean = st[0] / m
    var = st[1] / m - mean * mean
    inv = jax.lax.rsqrt(var + EPS)
    a = scale * inv
    return a, bias - mean * a


def xla_bottleneck_chain(x, params, side):
    """L real ResNet bottlenecks (1x1 C->c, 3x3 c->c, 1x1 c->C, BNs,
    relu, residual) in NCHW with the framework's BN math."""
    n, cc, h, w_ = x.shape
    m = n * h * w_

    def bn_relu(y, scale, bias, relu=True):
        yf = y.astype(jnp.float32)
        s1 = jnp.sum(yf, axis=(0, 2, 3))
        s2 = jnp.sum(yf * yf, axis=(0, 2, 3))
        a, b = _bn_coefs(jnp.stack([s1, s2]), m, scale, bias)
        out = yf * a.reshape(1, -1, 1, 1) + b.reshape(1, -1, 1, 1)
        return out if not relu else jnp.maximum(out, 0.0)

    def conv(x_, w_m, pad):
        return jax.lax.conv_general_dilated(
            x_, w_m, window_strides=(1, 1), padding=[(pad, pad)] * 2,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    for (w1, w2, w3, s1_, b1_, s2_, b2_, s3_, b3_) in params:
        t = bn_relu(conv(x, w1, 0), s1_, b1_).astype(x.dtype)
        t = bn_relu(conv(t, w2, 1), s2_, b2_).astype(x.dtype)
        t3 = conv(t, w3, 0)
        y = bn_relu(t3, s3_, b3_, relu=False)
        x = jnp.maximum(y + x.astype(jnp.float32), 0.0).astype(x.dtype)
    return x


def pallas_bottleneck_chain(x, params, side):
    """Same math fused: stats ride conv epilogues, BN affine+relu ride
    the next conv's prologue; only the residual join is an XLA pass."""
    m = x.shape[0]
    for (w1, w2, w3, s1_, b1_, s2_, b2_, s3_, b3_) in params:
        t1, st1 = conv1x1_bn_act(x, w1, stats=True, interpret=False)
        a1, b1 = _bn_coefs(st1, m, s1_, b1_)
        t2, st2 = conv3x3_bn_act(t1, w2, side, side, a=a1, b=b1,
                                 relu=True, stats=True, interpret=False)
        a2, b2 = _bn_coefs(st2, m, s2_, b2_)
        t3, st3 = conv1x1_bn_act(t2, w3, a=a2, b=b2, relu=True,
                                 stats=True, interpret=False)
        a3, b3 = _bn_coefs(st3, m, s3_, b3_)
        x = jnp.maximum(
            t3.astype(jnp.float32) * a3[None, :] + b3[None, :]
            + x.astype(jnp.float32), 0.0).astype(x.dtype)
    return x


def run_bottleneck(name, bs, big_c, small_c, side, rng, l_blocks=8):
    m = bs * side * side
    print(f"== {name}: bs{bs} {big_c}->{small_c} @ {side}x{side} "
          f"(M={m}, {l_blocks} bottleneck blocks) ==")

    def mk(shape, fan_in):
        return jnp.asarray(rng.randn(*shape) * (1.0 / np.sqrt(fan_in)),
                           jnp.bfloat16)

    nchw_params, flat_params = [], []
    for _ in range(l_blocks):
        w1 = mk((small_c, big_c, 1, 1), big_c)
        w2 = mk((small_c, small_c, 3, 3), small_c * 9)
        w3 = mk((big_c, small_c, 1, 1), small_c)
        bns = [jnp.ones(small_c, jnp.float32),
               jnp.zeros(small_c, jnp.float32),
               jnp.ones(small_c, jnp.float32),
               jnp.zeros(small_c, jnp.float32),
               jnp.ones(big_c, jnp.float32),
               jnp.zeros(big_c, jnp.float32)]
        nchw_params.append(tuple([w1, w2, w3] + bns))
        flat_params.append(tuple(
            [w1.reshape(small_c, big_c).T, pack_w3x3(w2),
             w3.reshape(big_c, small_c).T] + bns))
    x_nchw = jnp.asarray(rng.randn(bs, big_c, side, side), jnp.bfloat16)
    x_flat = jnp.asarray(
        np.transpose(np.asarray(x_nchw, np.float32),
                     (0, 2, 3, 1)).reshape(m, big_c), jnp.bfloat16)
    flops = l_blocks * 2.0 * m * (
        big_c * small_c * 2 + 9 * small_c * small_c)
    time_chain(functools.partial(xla_bottleneck_chain,
                                 params=nchw_params, side=side),
               x_nchw, flops, f"{name} bottleneck XLA")
    time_chain(functools.partial(pallas_bottleneck_chain,
                                 params=flat_params, side=side),
               x_flat, flops, f"{name} bottleneck Pallas")


def time_chain(fn, x0, flops_per_call, label):
    """Donated-arg self-chain + marginal timing (shared protocol)."""
    from common import time_chain as shared
    return shared(fn, x0, flops_per_call, label, n1=N1, n2=N2)


def main():
    configs = {
        "stage1": (128, 256, 56),    # bs, C, HW-side (square channels)
        "stage3": (128, 1024, 14),
    }
    bneck_configs = {
        "bneck1": (128, 256, 64, 56),    # bs, C, c, side
        "bneck2": (128, 512, 128, 28),
        "bneck3": (128, 1024, 256, 14),
        "bneck4": (128, 2048, 512, 7),
    }
    which = sys.argv[1:] or list(configs)
    rng = np.random.RandomState(0)
    for name in which:
        if name in bneck_configs:
            run_bottleneck(name, *bneck_configs[name], rng)
            continue
        bs, c, side = configs[name]
        m = bs * side * side
        print(f"== {name}: bs{bs} {c}x{side}x{side} (M={m}, K=N={c}, "
              f"L={L}) ==")
        ws_oihw = [jnp.asarray(
            rng.randn(c, c, 1, 1) * (1.0 / np.sqrt(c)), jnp.bfloat16)
            for _ in range(L)]
        ws_flat = [w.reshape(c, c).T for w in ws_oihw]
        scales = [jnp.ones(c, jnp.float32) for _ in range(L)]
        biases = [jnp.zeros(c, jnp.float32) for _ in range(L)]
        x_nchw = jnp.asarray(rng.randn(bs, c, side, side), jnp.bfloat16)
        x_flat = jnp.asarray(
            np.transpose(np.asarray(x_nchw, np.float32),
                         (0, 2, 3, 1)).reshape(m, c), jnp.bfloat16)
        flops = 2.0 * m * c * c * L
        time_chain(functools.partial(conv_only_xla, ws=ws_oihw),
                   x_nchw, flops, f"{name} conv-only XLA")
        time_chain(functools.partial(conv_only_pallas, ws=ws_flat),
                   x_flat, flops, f"{name} conv-only Pallas")
        time_chain(functools.partial(xla_chain, ws=ws_oihw,
                                     scales=scales, biases=biases),
                   x_nchw, flops, f"{name} conv+BN+relu XLA")
        time_chain(functools.partial(pallas_chain, ws=ws_flat,
                                     scales=scales, biases=biases),
                   x_flat, flops, f"{name} conv+BN+relu Pallas")


if __name__ == "__main__":
    main()
