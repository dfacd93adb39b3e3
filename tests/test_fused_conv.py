"""Pallas fused conv+BN kernels vs composed-op oracles (interpret mode
on CPU; the same kernels compile on TPU — see benchmarks/conv_kernel_ab.py
for the on-chip A/B)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas.fused_conv import (
    conv1x1_bn_act, conv3x3_bn_act, pack_w3x3,
    reference_conv1x1_bn_act)


def _rand(shape, seed, scale=1.0):
    return jnp.asarray(
        np.random.RandomState(seed).randn(*shape) * scale, jnp.bfloat16)


def _conv3x3_oracle(x_flat, w_oihw, nb, h, w, a=None, b=None,
                    relu=False):
    c = x_flat.shape[1]
    xf = x_flat.astype(jnp.float32)
    if a is not None:
        xf = xf * a[None, :] + b[None, :]
        if relu:
            xf = jnp.maximum(xf, 0.0)
        xf = xf.astype(x_flat.dtype).astype(jnp.float32)
    elif relu:
        xf = jnp.maximum(xf, 0.0)
    xn = xf.reshape(nb, h, w, c).transpose(0, 3, 1, 2)
    out = jax.lax.conv_general_dilated(
        xn, jnp.asarray(w_oihw, jnp.float32), (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return out.transpose(0, 2, 3, 1).reshape(-1, w_oihw.shape[0])


@pytest.mark.parametrize("kwargs", [
    {}, {"relu": True}, {"affine": True}, {"affine": True, "relu": True},
])
def test_conv1x1_matches_oracle(kwargs):
    m, k, n = 256, 64, 128
    x, w = _rand((m, k), 0), _rand((k, n), 1, 0.1)
    kw = dict(kwargs)
    if kw.pop("affine", False):
        rng = np.random.RandomState(2)
        kw["a"] = jnp.asarray(rng.rand(k) + 0.5, jnp.float32)
        kw["b"] = jnp.asarray(rng.randn(k) * 0.1, jnp.float32)
    o1, s1 = conv1x1_bn_act(x, w, block_m=64, **kw)
    o2, s2 = reference_conv1x1_bn_act(x, w, **kw)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=2e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-3, atol=1.0)


def test_conv1x1_no_stats():
    x, w = _rand((128, 64), 0), _rand((64, 64), 1, 0.1)
    out, st = conv1x1_bn_act(x, w, stats=False, block_m=64)
    assert st is None
    ref, _ = reference_conv1x1_bn_act(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=1e-2)


@pytest.mark.parametrize("c,block_m", [
    (32, 32),   # direct halo-DMA path
    (64, 24),   # pixel-pair packed path (C=64 -> 128-lane geometry)
])
def test_conv3x3_matches_oracle(c, block_m):
    nb, h, w, co = 2, 8, 8, 48
    x = _rand((nb * h * w, c), 0)
    w_oihw = _rand((co, c, 3, 3), 1, 0.08)
    wf = pack_w3x3(w_oihw)
    rng = np.random.RandomState(2)
    a = jnp.asarray(rng.rand(c) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(c) * 0.1, jnp.float32)
    for kw in ({}, {"a": a, "b": b, "relu": True}):
        o1, s1 = conv3x3_bn_act(x, wf, h, w, stats=True,
                                block_m=block_m, **kw)
        o2 = _conv3x3_oracle(x, w_oihw, nb, h, w, **kw)
        np.testing.assert_allclose(np.asarray(o1, np.float32),
                                   np.asarray(o2), rtol=6e-2, atol=4e-1)
        s2 = np.stack([np.asarray(o2).sum(0),
                       (np.asarray(o2) ** 2).sum(0)])
        np.testing.assert_allclose(np.asarray(s1), s2, rtol=4e-2,
                                   atol=4.0)


def test_conv3x3_small_fallback():
    """Tiny inputs route to the jnp fallback (bm <= halo)."""
    nb, h, w, c, co = 2, 8, 8, 32, 16
    x = _rand((nb * h * w, c), 0)
    w_oihw = _rand((co, c, 3, 3), 1, 0.1)
    o1, s1 = conv3x3_bn_act(x, pack_w3x3(w_oihw), h, w, block_m=8)
    o2 = _conv3x3_oracle(x, w_oihw, nb, h, w)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2), rtol=5e-2, atol=2e-1)
    assert s1.shape == (2, co)


def test_strided_1x1_conv_subsample_rewrite_exact():
    """ops/nn_ops.py lowers a strided 1x1 conv to subsample + stride-1
    conv (clean MXU gradients); forward must be bit-identical to the
    strided lax.conv and gradients must match autodiff of it."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 8, 10, 10), jnp.float32)
    w = jnp.asarray(rng.randn(16, 8, 1, 1) * 0.2, jnp.float32)
    from paddle_tpu.ops.nn_ops import _conv2d_impl

    def direct(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (2, 2), [(0, 0), (0, 0)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    y1 = _conv2d_impl(x, w, (2, 2), (0, 0), (1, 1), 1)
    y2 = direct(x, w)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-6, atol=1e-6)
    g1 = jax.grad(lambda x, w: jnp.sum(
        jnp.sin(_conv2d_impl(x, w, (2, 2), (0, 0), (1, 1), 1))),
        argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda x, w: jnp.sum(jnp.sin(direct(x, w))),
                  argnums=(0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_bn_autodiff_matches_custom_vjp_grads():
    """Round-3 change: batch_norm's train path is left to autodiff so
    XLA can fuse its backward into conv gradient fusions; the round-2
    custom_vjp stays available (PADDLE_TPU_BN_CUSTOM_VJP=1) and both
    must produce the same gradients."""
    from paddle_tpu.ops.nn_ops import _bn_train, _bn_train_custom
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 3, 5, 5), jnp.float32)
    scale = jnp.asarray(rng.rand(3) + 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(3), jnp.float32)

    def loss(fn, x, s, b):
        return jnp.sum(jnp.sin(fn(x, s, b, (0, 2, 3), 1e-5)))

    g1 = jax.grad(lambda *a: loss(_bn_train, *a), argnums=(0, 1, 2))(
        x, scale, bias)
    g2 = jax.grad(lambda *a: loss(_bn_train_custom, *a),
                  argnums=(0, 1, 2))(x, scale, bias)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
