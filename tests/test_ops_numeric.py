"""Numeric-gradient + NumPy-oracle checks for representative ops
(reference: the 202 per-op unittests built on op_test.py; this battery
covers one op per family — dense math, conv, norm, softmax/xent, pooling,
embedding lookup, sequence/ragged, broadcasting elementwise, reduction)."""
import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.lod import LoDTensor, RaggedPair
from op_test import OpTestHarness


def _r(shape, seed=0, scale=1.0):
    return (np.random.RandomState(seed).uniform(-1, 1, shape) * scale
            ).astype(np.float32)


def test_mul_op():
    x, y = _r((4, 6), 0), _r((6, 3), 1)
    t = OpTestHarness("mul", {"X": ("x", x), "Y": ("y", y)})
    t.check_output({"Out": x @ y})
    t.check_grad(["x", "y"])


def test_elementwise_add_broadcast():
    x, y = _r((4, 5), 0), _r((5,), 1)
    t = OpTestHarness("elementwise_add", {"X": ("x", x), "Y": ("y", y)},
                      attrs={"axis": -1})
    t.check_output({"Out": x + y})
    t.check_grad(["x", "y"])


def test_relu_op():
    x = _r((3, 7), 2)
    t = OpTestHarness("relu", {"X": ("x", x)})
    t.check_output({"Out": np.maximum(x, 0)})
    # keep eps below the smallest |x| near 0 to avoid kink crossings
    t.check_grad(["x"], eps=1e-3, max_relative_error=2e-2)


def test_softmax_op():
    x = _r((4, 8), 3)
    e = np.exp(x - x.max(-1, keepdims=True))
    t = OpTestHarness("softmax", {"X": ("x", x)})
    t.check_output({"Out": e / e.sum(-1, keepdims=True)})
    t.check_grad(["x"], max_relative_error=1e-2)


def test_softmax_with_cross_entropy():
    logits = _r((5, 7), 4, 2.0)
    labels = np.random.RandomState(5).randint(0, 7, (5, 1)).astype(np.int64)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    expected = -np.log(p[np.arange(5), labels[:, 0]])[:, None]
    t = OpTestHarness("softmax_with_cross_entropy",
                      {"Logits": ("logits", logits),
                       "Label": ("label", labels)},
                      out_slots=("Loss",))
    t.check_output({"Loss": expected}, atol=1e-4, rtol=1e-4)
    t.check_grad(["logits"], output_slot="Loss", max_relative_error=1e-2)


def test_conv2d_op():
    x, w = _r((2, 3, 8, 8), 6), _r((4, 3, 3, 3), 7)
    t = OpTestHarness("conv2d", {"Input": ("x", x), "Filter": ("w", w)},
                      attrs={"strides": [1, 1], "paddings": [1, 1],
                             "dilations": [1, 1], "groups": 1},
                      out_slots=("Output",))
    # oracle via scipy-free direct conv
    def conv(x, w, pad):
        xp = np.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
        n, c, h, ww = x.shape
        oc = w.shape[0]
        out = np.zeros((n, oc, h, ww), np.float64)
        for i in range(3):
            for j in range(3):
                patch = xp[:, :, i:i + h, j:j + ww]
                out += np.einsum("nchw,oc->nohw", patch, w[:, :, i, j])
        return out
    t.check_output({"Output": conv(x, w, 1)}, atol=1e-4, rtol=1e-4)
    t.check_grad(["x", "w"], output_slot="Output",
                 max_relative_error=1e-2)


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


def test_pool2d_max():
    x = _r((2, 2, 6, 6), 8)
    t = OpTestHarness("pool2d", {"X": ("x", x)},
                      attrs={"pooling_type": "max", "ksize": [2, 2],
                             "strides": [2, 2], "paddings": [0, 0]})
    exp = x.reshape(2, 2, 3, 2, 3, 2).max(axis=(3, 5))
    t.check_output({"Out": exp})
    t.check_grad(["x"], max_relative_error=1e-2)


def test_layer_norm_op():
    x = _r((4, 10), 9, 2.0)
    scale, bias = _r((10,), 10), _r((10,), 11)
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    exp = (x - mu) / np.sqrt(var + 1e-5) * scale + bias
    t = OpTestHarness("layer_norm",
                      {"X": ("x", x), "Scale": ("scale", scale),
                       "Bias": ("bias", bias)},
                      attrs={"epsilon": 1e-5, "begin_norm_axis": 1},
                      out_slots=("Y",))
    t.check_output({"Y": exp}, atol=1e-4, rtol=1e-3)
    t.check_grad(["x", "scale", "bias"], output_slot="Y",
                 max_relative_error=1.5e-2)


def test_lookup_table_grad():
    table = _r((20, 6), 12)
    ids = np.random.RandomState(13).randint(0, 20, (4, 1)).astype(np.int64)
    t = OpTestHarness("lookup_table",
                      {"W": ("w", table), "Ids": ("ids", ids)})
    t.check_output({"Out": table[ids[:, 0]]})
    t.check_grad(["w"])


def test_reduce_mean_keepdim():
    x = _r((3, 4, 5), 14)
    t = OpTestHarness("reduce_mean", {"X": ("x", x)},
                      attrs={"dim": [1], "keep_dim": True})
    t.check_output({"Out": x.mean(1, keepdims=True)})
    t.check_grad(["x"])


def test_sequence_pool_ragged_grad():
    rng = np.random.RandomState(15)
    seqs = [rng.uniform(-1, 1, (n, 3)).astype(np.float32)
            for n in (4, 2, 5)]
    lod = LoDTensor.from_sequences(seqs)
    padded, lengths = lod.to_padded(max_len=6)
    rp = RaggedPair(padded, lengths)
    t = OpTestHarness("sequence_pool", {"X": ("x", rp)},
                      attrs={"pooltype": "average"})
    exp = np.stack([s.mean(0) for s in seqs])
    t.check_output({"Out": exp}, atol=1e-5, rtol=1e-4)
    t.check_grad(["x"], max_relative_error=1e-2)


def test_tanh_and_sigmoid():
    x = _r((4, 4), 16)
    t = OpTestHarness("tanh", {"X": ("x", x)})
    t.check_output({"Out": np.tanh(x)})
    t.check_grad(["x"])
    t = OpTestHarness("sigmoid", {"X": ("x", x)})
    t.check_output({"Out": 1 / (1 + np.exp(-x))})
    t.check_grad(["x"])


def test_top_k_output():
    x = _r((3, 10), 17)
    t = OpTestHarness("top_k", {"X": ("x", x)}, attrs={"k": 3},
                      out_slots=("Out", "Indices"),
                      out_dtypes={"Indices": "int64"})
    got = t.outputs()
    exp_idx = np.argsort(-x, axis=1)[:, :3]
    np.testing.assert_allclose(got["Out"],
                               np.take_along_axis(x, exp_idx, 1),
                               atol=1e-6)
    np.testing.assert_array_equal(got["Indices"], exp_idx)


def test_sequence_softmax_ragged_output_grad():
    """Ragged OUTPUT slot: the harness must weight the padded in-graph
    shape, not the flat LoDTensor fetch."""
    rng = np.random.RandomState(18)
    seqs = [rng.uniform(-1, 1, (n, 1)).astype(np.float32)
            for n in (3, 5, 2)]
    lod = LoDTensor.from_sequences(seqs)
    padded, lengths = lod.to_padded(max_len=6)
    rp = RaggedPair(padded, lengths)
    t = OpTestHarness("sequence_softmax", {"X": ("x", rp)})
    got = t.outputs()["Out"]           # flat steps [sum_len, 1]
    exp = np.concatenate([np.exp(s) / np.exp(s).sum() for s in seqs])
    np.testing.assert_allclose(got, exp, atol=1e-5, rtol=1e-4)
    t.check_grad(["x"], max_relative_error=1.5e-2)
