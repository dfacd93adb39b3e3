"""Pallas flash-attention kernel vs naive attention (interpret mode on CPU).

The reference's analogue of this layer is its hand-fused CUDA library
(paddle/cuda/src/hl_cuda_lstm.cu etc.); kernels are validated against the
composed-op oracle the same way op_test validates ops against NumPy.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import flash_attention


def naive(q, k, v, bias=None, causal=False):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if bias is not None:
        s = s + bias
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        m = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(m[None, None], s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def _rand(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("use_bias", [False, True])
def test_flash_matches_naive(causal, use_bias):
    B, H, S, D = 2, 2, 80, 16
    q, k, v = _rand((B, H, S, D), 0), _rand((B, H, S, D), 1), \
        _rand((B, H, S, D), 2)
    bias = None
    if use_bias:
        mask = np.random.RandomState(3).rand(B, 1, S, S) < 0.1
        bias = jnp.asarray(np.where(mask, -1e9, 0.0), jnp.float32)
    o1 = flash_attention(q, k, v, bias, causal=causal,
                         block_q=32, block_k=32, interpret=True)
    o2 = naive(q, k, v, bias, causal=causal)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=1e-5, rtol=1e-5)


def test_flash_grads_match_naive():
    B, H, S, D = 1, 2, 64, 16
    q, k, v = _rand((B, H, S, D), 0), _rand((B, H, S, D), 1), \
        _rand((B, H, S, D), 2)
    bias = jnp.asarray(
        np.where(np.random.RandomState(3).rand(B, 1, S, S) < 0.1,
                 -1e9, 0.0), jnp.float32)

    def loss_flash(q, k, v, b):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, b, block_q=32, block_k=32, interpret=True,
            bias_grad=True)))

    def loss_naive(q, k, v, b):
        return jnp.sum(jnp.sin(naive(q, k, v, b)))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, bias)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, b in zip(g1, g2):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("bias_shape", [(1, 2, 1, 64), (1, 1, 64, 64),
                                        (1, 2, 64, 1)])
def test_trainable_bias_broadcast_grad(bias_shape):
    """dbias must be summed over every broadcast dim (trainable
    relative-position-style biases)."""
    B, H, S, D = 2, 2, 64, 16
    q, k, v = _rand((B, H, S, D), 0), _rand((B, H, S, D), 1), \
        _rand((B, H, S, D), 2)
    bias = _rand(bias_shape, 3) * 0.1

    def loss_flash(b):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, b, block_q=32, block_k=32, interpret=True,
            bias_grad=True)))

    def loss_naive(b):
        return jnp.sum(jnp.sin(naive(q, k, v, b)))

    g1, g2 = jax.grad(loss_flash)(bias), jax.grad(loss_naive)(bias)
    assert g1.shape == bias.shape
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("S", [80, 100])
def test_causal_flag_and_key_row_bias_equal_the_dense_bias(S):
    """What models/transformer.py hands the decoder's self-attention
    (causal=True and a [b,1,1,S] pad mask) against the [b,1,S,S] sum of
    triangle and pad mask it handed before ISSUE 32: forward, dq, dk,
    dv, over 3 or 4 tiles each way of a length the tile does not
    divide, one row half pads."""
    B, H, D = 2, 2, 16
    q, k, v, w = (_rand((B, H, S, D), i) for i in range(4))
    pad = np.zeros((B, 1, 1, S), np.float32)
    pad[0, ..., S - 5:] = -1e9
    pad[1, ..., S // 2:] = -1e9
    tri = np.triu(np.full((S, S), -1e9, np.float32), k=1)

    def run(bias, causal):
        def loss(q, k, v):
            o = flash_attention(q, k, v, jnp.asarray(bias), causal=causal,
                                block_q=32, block_k=32, interpret=True)
            return jnp.sum(o * w), o
        grads, o = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (o, *grads)

    for name, a, b in zip(("out", "dq", "dk", "dv"), run(pad, True),
                          run(pad + tri, False)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6, err_msg=name)


def _paths(which):
    """Counter of path: flash `fwd` or `bwd` calls traced so far."""
    import collections

    from paddle_tpu.observability import default_registry
    fam = default_registry().get(f"paddle_tpu_flash_{which}_sites_total")
    by_path = collections.Counter()     # over every window and group
    for labels, child in (fam.samples() if fam is not None else ()):
        by_path[labels[0]] += child.value
    return by_path


# (sq, sk, d, d_v, tile cap): lengths the tiles do not divide, a value
# head of another width, more keys than queries and fewer, and five
# tiles each way of 1100
_FUSED_DIMS = [(80, 80, 16, 16, 32), (100, 100, 16, 24, 32),
               (40, 72, 16, 16, 32), (72, 40, 24, 16, 32),
               (1100, 1100, 16, 16, 256)]


@pytest.mark.parametrize("budget", [None, 0], ids=["resident", "partial"])
@pytest.mark.parametrize("dims", _FUSED_DIMS,
                         ids=lambda d: "x".join(map(str, d)))
@pytest.mark.parametrize("bias_kind", ["none", "key_row", "dense",
                                       "trainable_head_key",
                                       "trainable_query_key"])
@pytest.mark.parametrize("causal", [False, True])
def test_fused_backward_matches_naive(causal, bias_kind, dims, budget,
                                      monkeypatch):
    """The one backward kernel (ISSUE 38) against jax.grad of the
    composition: dq, dk, dv, and dbias where the bias is trained, with a
    head's K and V resident and — the budget taken away — a k-block at a
    time, where every q-block writes an f32 partial dQ a k-block and a
    causal site's skipped tiles must write their zeros."""
    import importlib
    sq, sk, d, dv, cap = dims
    B, H = (1, 2) if sq > 1000 else (2, 2)
    if sq > 1000 and bias_kind not in ("none", "key_row"):
        pytest.skip("the long case runs the cells' two mask kinds")
    if budget is not None:
        monkeypatch.setattr(importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention"), "_VMEM_BUDGET",
            budget)
    q, k = _rand((B, H, sq, d), 0), _rand((B, H, sk, d), 1)
    v, w = _rand((B, H, sk, dv), 2), _rand((B, H, sq, dv), 3)
    bias = _fused_bias(bias_kind, B, H, sq, sk)
    trained = bias_kind.startswith("trainable")
    argnums = (0, 1, 2, 3) if trained else (0, 1, 2)

    def loss_flash(q, k, v, b):
        return jnp.sum(w * flash_attention(
            q, k, v, b, causal=causal, block_q=cap, block_k=cap,
            interpret=True, bias_grad=trained))

    def loss_naive(q, k, v, b):
        return jnp.sum(w * naive(q, k, v, b, causal=causal))

    paths = _paths("bwd")
    got = jax.grad(loss_flash, argnums)(q, k, v, bias)
    segments = -(-sk // cap) if budget == 0 else 1
    assert _paths("bwd") - paths == {
        "resident" if segments == 1 else "partial": 1}
    want = jax.grad(loss_naive, argnums)(q, k, v, bias)
    for name, a, b in zip(("dq", "dk", "dv", "dbias"), got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4, err_msg=name)


def _fused_bias(bias_kind, B, H, sq, sk):
    rng = np.random.RandomState(4)
    bias = {
        "none": None,
        "key_row": np.where(rng.rand(B, 1, 1, sk) < 0.2, -1e9, 0.0),
        "dense": np.where(rng.rand(B, 1, sq, sk) < 0.1, -1e9, 0.0),
        "trainable_head_key": rng.randn(1, H, 1, sk) * 0.1,
        "trainable_query_key": rng.randn(1, 1, sq, sk) * 0.1,
    }[bias_kind]
    if bias is not None:
        bias = np.asarray(bias, np.float32)
        if not bias_kind.startswith("trainable"):
            bias[..., 0] = 0.0      # no row without a key
    return bias


@pytest.mark.parametrize("budget", [None, 0], ids=["resident", "partial"])
@pytest.mark.parametrize("dims", _FUSED_DIMS,
                         ids=lambda d: "x".join(map(str, d)))
@pytest.mark.parametrize("bias_kind", ["none", "key_row", "dense",
                                       "trainable_head_key",
                                       "trainable_query_key"])
@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_naive(causal, bias_kind, dims, budget, monkeypatch):
    """The forward kernel (ISSUE 40: one grid step a q-block, the k-blocks
    walked inside it, the statistics carried between tiles as rows)
    against the f32 composition: `o` and the logsumexp it leaves the
    backward, with a head's K and V resident and — the budget taken away
    — a k-block a grid step, the statistics and the accumulator waiting
    in scratch between them."""
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    sq, sk, d, dv, cap = dims
    B, H = (1, 2) if sq > 1000 else (2, 2)
    if sq > 1000 and bias_kind not in ("none", "key_row"):
        pytest.skip("the long case runs the cells' two mask kinds")
    if budget is not None:
        monkeypatch.setattr(fa, "_VMEM_BUDGET", budget)
    q, k = _rand((B, H, sq, d), 0), _rand((B, H, sk, d), 1)
    v = _rand((B, H, sk, dv), 2)
    bias = _fused_bias(bias_kind, B, H, sq, sk)
    paths = _paths("fwd")
    o, lse = fa._fwd(q, k, v, None if bias is None else jnp.asarray(bias),
                     1.0 / np.sqrt(d), causal, None, cap, cap, True,
                     bias_kind.startswith("trainable"))
    segments = -(-sk // cap) if budget == 0 else 1
    assert _paths("fwd") - paths == {
        "resident" if segments == 1 else "partial": 1}
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if bias is not None:
        s = s + bias
    if causal:
        s = jnp.where(jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :],
                      s, -1e30)
    assert o.shape == (B, H, sq, dv) and lse.shape == (B, H, sq)
    assert lse.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(o),
        np.asarray(jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)),
        atol=1e-5, rtol=1e-5, err_msg="o")
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.scipy.special.logsumexp(s, -1)),
        atol=1e-5, rtol=1e-5, err_msg="lse")


@pytest.mark.parametrize("budget", [None, 0], ids=["resident", "partial"])
@pytest.mark.parametrize("S", [64, 80])
def test_forward_gives_fully_masked_rows_zero(S, budget, monkeypatch):
    """A query none of whose keys is open (every key of batch 0 at -inf)
    reads 0, not the NaN of the composition's 0 / 0, and its gradients
    are finite; the other batch is untouched by it. (Not so under
    causal=True, now as before: the keys above the diagonal carry the
    finite -1e30, and a row with nothing larger averages them.)"""
    import importlib
    if budget is not None:
        monkeypatch.setattr(importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention"), "_VMEM_BUDGET",
            budget)
    B, H, D = 2, 2, 16
    q, k, v = (_rand((B, H, S, D), i) for i in range(3))
    bias = np.zeros((B, 1, 1, S), np.float32)
    bias[0] = -np.inf

    def attend(q, k, v):
        return flash_attention(q, k, v, jnp.asarray(bias), block_q=32,
                               block_k=32, interpret=True)

    o = attend(q, k, v)
    assert not np.asarray(o[0]).any()
    np.testing.assert_allclose(np.asarray(o[1]),
                               np.asarray(naive(q, k, v)[1]),
                               atol=1e-5, rtol=1e-5)
    for g in jax.grad(lambda *a: jnp.sum(jnp.sin(attend(*a))),
                      (0, 1, 2))(q, k, v):
        assert np.isfinite(np.asarray(g)).all()


def test_flash_uneven_kv_len():
    # Sq != Sk and not multiples of the block size: padding must be masked.
    B, H, Sq, Sk, D = 1, 1, 40, 72, 16
    q = _rand((B, H, Sq, D), 0)
    k, v = _rand((B, H, Sk, D), 1), _rand((B, H, Sk, D), 2)
    o1 = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    o2 = naive(q, k, v)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=1e-5, rtol=1e-5)


def test_sdpa_op_flash_flag():
    """The fused op's use_flash attr routes through the Pallas kernel."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.layer_helper import LayerHelper

    B, H, S, D = 2, 2, 32, 8
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        q = layers.data("q", [H, S, D], dtype="float32")
        helper = LayerHelper("sdpa")
        out_flash = helper.create_tmp_variable("float32")
        out_naive = helper.create_tmp_variable("float32")
        helper.append_op(type="scaled_dot_product_attention",
                         inputs={"Q": q, "K": q, "V": q},
                         outputs={"Out": out_flash},
                         attrs={"use_flash": True})
        helper.append_op(type="scaled_dot_product_attention",
                         inputs={"Q": q, "K": q, "V": q},
                         outputs={"Out": out_naive},
                         attrs={"use_flash": False})
    exe = pt.Executor()
    exe.run(startup)
    qv = np.random.RandomState(0).randn(B, H, S, D).astype(np.float32)
    a, b = exe.run(main, feed={"q": qv},
                   fetch_list=[out_flash, out_naive])
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("seq,expect_flash", [
    (512, True), (256, True), (255, False), (128, False)])
def test_attention_routing_threshold(monkeypatch, seq, expect_flash):
    """Verify WHICH attention path runs. The routing threshold puts
    flash ahead from S = 256 (ISSUE 51; 512 until then), so on a TPU
    backend the sdpa op must dispatch the Pallas kernel at S >= 256
    (the bench transformer's S = 256 among them) and keep the naive
    composition below (the token server's 128-token prefill bucket)."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.layer_helper import LayerHelper
    from paddle_tpu.ops import nn_ops
    import paddle_tpu.ops.pallas as pallas_pkg

    calls = []

    def fake_flash(q, k, v, bias=None, causal=False, **kw):
        calls.append(q.shape)
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        return jnp.einsum("bhqk,bhkd->bhqd",
                          jax.nn.softmax(s, -1), v)

    monkeypatch.setattr(pallas_pkg, "flash_attention", fake_flash)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    pt.reset_default_programs()
    pt.reset_global_scope()
    B, H, D = 2, 8, 64
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        q = layers.data("q", [H, seq, D], dtype="float32")
        helper = LayerHelper("sdpa")
        out = helper.create_tmp_variable("float32")
        helper.append_op(type="scaled_dot_product_attention",
                         inputs={"Q": q, "K": q, "V": q},
                         outputs={"Out": out},
                         attrs={"causal": True})
    exe = pt.Executor()
    exe.run(startup)
    qv = np.random.RandomState(0).randn(B, H, seq, D).astype(
        np.float32)
    exe.run(main, feed={"q": qv}, fetch_list=[out])
    assert bool(calls) == expect_flash, (seq, calls)


@pytest.mark.parametrize("with_mask", [False, True])
def test_per_shard_attention_matches_unsharded_kernel(with_mask):
    """Under a mesh (ParallelExecutor) the sdpa op runs the flash kernel
    inside shard_map over batch and heads; values must not change."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from paddle_tpu.ops.nn_ops import _per_shard_attention
    from paddle_tpu.ops.pallas import flash_attention

    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(4, 4, 16, 8), jnp.float32)
               for _ in range(3))
    mask = None
    if with_mask:
        pad = np.zeros((4, 1, 1, 16), np.float32)
        pad[:, :, :, 12:] = -1e9
        mask = jnp.asarray(pad)
    attend = functools.partial(flash_attention, causal=True,
                               interpret=True)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    sharded = functools.partial(_per_shard_attention, attend, mesh,
                                batch_axis="data", head_axis="model")
    got = jax.jit(sharded)(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(attend(q, k, v, mask)),
                               atol=1e-6)
    # a dim its axis does not divide stays replicated (3 heads over 2)
    q3, k3, v3 = q[:, :3], k[:, :3], v[:, :3]
    got3 = sharded(q3, k3, v3, mask)
    np.testing.assert_allclose(np.asarray(got3),
                               np.asarray(attend(q3, k3, v3, mask)),
                               atol=1e-6)
