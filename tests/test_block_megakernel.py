"""Batch-tiled bottleneck megakernel: interpret-mode correctness vs the
jnp ghost-BN oracle (the on-chip perf A/B lives in
benchmarks/block_megakernel_ab.py)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.block_megakernel import (
    bottleneck_block, bottleneck_block_reference)


def _mk(n=4, h=6, w=6, cin=256, cm=128, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, h * w, cin) * 0.5, dtype)
    w1 = jnp.asarray(rng.randn(cin, cm) / np.sqrt(cin), dtype)
    w3 = jnp.asarray(rng.randn(9, cm, cm) / np.sqrt(9 * cm), dtype)
    w2 = jnp.asarray(rng.randn(cm, cin) / np.sqrt(cm), dtype)
    bns = [np.stack([rng.rand(c) + 0.5, rng.randn(c) * 0.1])
           for c in (cm, cm, cin)]
    return x, w1, w3, w2, bns


@pytest.mark.parametrize("tile", [1, 2])
def test_megakernel_matches_oracle(tile):
    x, w1, w3, w2, bns = _mk()
    y = bottleneck_block(x, w1, w3, w2, *bns, h_img=6, w_img=6,
                         tile=tile, interpret=True)
    ref = bottleneck_block_reference(x, w1, w3, w2, *bns, h_img=6,
                                     w_img=6, tile=tile)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_megakernel_tap_orientation():
    """A single bright pixel must blur to its 3x3 neighbourhood with
    the matching tap weights — pins the roll sign and mask logic."""
    n, h, w, cin, cm = 2, 6, 6, 128, 128
    x = np.zeros((n, h * w, cin), np.float32)
    x[0, 2 * w + 3, :] = 1.0   # image 0, (h=2, w=3)
    x = jnp.asarray(x)
    w1 = jnp.eye(cin, cm, dtype=jnp.float32)
    # tap t scales by t+1 so each neighbour is identifiable
    w3 = jnp.stack([jnp.eye(cm, dtype=jnp.float32) * (t + 1)
                    for t in range(9)])
    w2 = jnp.eye(cm, cin, dtype=jnp.float32)
    # identity BNs: gamma=1, beta=0 -> but ghost stats still normalize;
    # use the oracle as ground truth rather than hand-computing
    bns = [np.stack([np.ones(c), np.zeros(c)]) for c in (cm, cm, cin)]
    y = bottleneck_block(x, w1, w3, w2, *bns, h_img=h, w_img=w,
                         tile=1, interpret=True)
    ref = bottleneck_block_reference(x, w1, w3, w2, *bns, h_img=h,
                                     w_img=w, tile=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # image 1 saw no signal; after ghost BN it is beta-constant rows,
    # so its output must be spatially uniform
    img1 = np.asarray(y[1])
    np.testing.assert_allclose(img1 - img1[0:1, :], 0.0, atol=1e-5)


def test_megakernel_edge_masking():
    """Bright pixel at a corner: taps reaching outside the image must
    contribute zero (no wraparound from the row rotation)."""
    n, h, w, cin, cm = 2, 6, 6, 128, 128
    x = np.zeros((n, h * w, cin), np.float32)
    x[0, 0, :] = 1.0           # corner (0, 0)
    x[1, (h - 1) * w + (w - 1), :] = 1.0   # far corner of image 1
    x = jnp.asarray(x)
    rng = np.random.RandomState(1)
    w1 = jnp.asarray(rng.randn(cin, cm).astype(np.float32) * 0.1)
    w3 = jnp.asarray(rng.randn(9, cm, cm).astype(np.float32) * 0.1)
    w2 = jnp.asarray(rng.randn(cm, cin).astype(np.float32) * 0.1)
    bns = [np.stack([np.ones(c), np.zeros(c)]) for c in (cm, cm, cin)]
    y = bottleneck_block(x, w1, w3, w2, *bns, h_img=h, w_img=w,
                         tile=2, interpret=True)
    ref = bottleneck_block_reference(x, w1, w3, w2, *bns, h_img=h,
                                     w_img=w, tile=2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
