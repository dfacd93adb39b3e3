"""The flash kernels with a value head of another width than the key head
(latent attention: 192-wide keys, 128-wide values): forward and all three
gradients against the matmul-softmax-matmul composition, causal and not,
in interpret mode; the SDPA op's two routes and the per-shard route under
a mesh; and, at ``d_v == d``, the arrays the kernels gave BEFORE they
were widened, bit for bit.

``tests/fixtures/flash_kernels_at_e172dd5.npz`` was recorded at commit
e172dd5 (the parent of the PR that widened the kernels) on the CPU in
interpret mode by the loop of ``_parent_case`` below: ``o`` and
``jax.grad`` of ``sum(o * cot)`` for q, k, v [1, 2, 40, 16] from
``RandomState(36)``, 16 x 16 tiles, with and without a key-row bias."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core.registry import grad_var_name
from paddle_tpu.models.transformer import _sdpa_op
from paddle_tpu.ops.pallas.flash_attention import flash_attention

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "flash_kernels_at_e172dd5.npz")


def _composed(q, k, v, causal):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        keep = jnp.arange(s.shape[-2])[:, None] >= \
            jnp.arange(s.shape[-1])[None, :]
        s = jnp.where(keep, s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def _qkv(d, d_v, seq=40, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(1, 2, seq, d).astype(np.float32),
            rng.randn(1, 2, seq, d).astype(np.float32),
            rng.randn(1, 2, seq, d_v).astype(np.float32),
            rng.randn(1, 2, seq, d_v).astype(np.float32))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("d,d_v", [(192, 128), (24, 16)])
def test_forward_matches_the_composition(d, d_v, causal):
    q, k, v, _ = _qkv(d, d_v)
    got = flash_attention(q, k, v, causal=causal, interpret=True,
                          block_q=16, block_k=16)
    assert got.shape == (1, 2, 40, d_v)
    np.testing.assert_allclose(got, _composed(q, k, v, causal),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("d,d_v", [(192, 128), (24, 16)])
def test_all_three_gradients_match_the_composition(d, d_v, causal):
    q, k, v, cot = _qkv(d, d_v, seed=1)

    def through(attend):
        return jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v) * cot),
                        (0, 1, 2))(q, k, v)

    got = through(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, interpret=True, block_q=16, block_k=16))
    want = through(lambda q, k, v: _composed(q, k, v, causal))
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5,
                                   err_msg=name)


def test_scale_stays_the_key_widths():
    """1/sqrt(d) of Q and K, whatever V's width: doubling d_v by
    zero columns changes nothing but the output's zero columns."""
    q, k, v, _ = _qkv(24, 16)
    wide = np.concatenate([v, np.zeros_like(v)], -1)
    a = flash_attention(q, k, v, interpret=True, block_q=16, block_k=16)
    b = flash_attention(q, k, wide, interpret=True, block_q=16, block_k=16)
    np.testing.assert_allclose(b[..., :16], a, rtol=1e-5, atol=1e-6)
    assert not np.asarray(b[..., 16:]).any()


def _parent_case(causal, with_bias):
    rng = np.random.RandomState(36)
    q, k, v = (rng.randn(1, 2, 40, 16).astype(np.float32)
               for _ in range(3))
    bias = np.where(rng.rand(1, 1, 1, 40) < 0.2, -1e9, 0.0).astype(
        np.float32) if with_bias else None
    cot = rng.randn(1, 2, 40, 16).astype(np.float32)

    def attend(q, k, v):
        return flash_attention(q, k, v, bias, causal=causal,
                               interpret=True, block_q=16, block_k=16)

    grads = jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v) * cot),
                     (0, 1, 2))(q, k, v)
    return dict(zip(("o", "dq", "dk", "dv"), (attend(q, k, v),) + grads))


@pytest.mark.parametrize("with_bias", [False, True])
@pytest.mark.parametrize("causal", [False, True])
def test_equal_widths_give_the_parents_arrays_bit_for_bit(causal,
                                                          with_bias):
    """Held to a few f32 ulps of arrays of magnitude 1-4, not bit for
    bit any more. The arrays were recorded from kernels that held a
    score tile q-rows-down: the one backward kernel (ISSUE 38) and then
    the forward (ISSUE 40, `o`: 1.2e-7 at most here) form the same
    products keys-down and accumulate `o` turned, so the CPU's dots
    round another way."""
    recorded = np.load(FIXTURE)
    tag = f"causal{int(causal)}_bias{int(with_bias)}"
    for name, got in _parent_case(causal, with_bias).items():
        np.testing.assert_allclose(np.asarray(got),
                                   recorded[f"{tag}_{name}"], rtol=0,
                                   atol=2e-6, err_msg=f"{tag} {name}")


def _op_program(d, d_v, seq):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        q = layers.data("q", [2, seq, d], stop_gradient=False)
        k = layers.data("k", [2, seq, d], stop_gradient=False)
        v = layers.data("v", [2, seq, d_v], stop_gradient=False)
        out = _sdpa_op(q, k, v, None, causal=True)
        loss = layers.reduce_sum(layers.elementwise_mul(out, out))
        pt.append_backward(loss, program=main)
    return main, out


@pytest.mark.parametrize("knob", ["force", "0"])
def test_the_ops_two_routes_take_a_narrower_value(monkeypatch, knob):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", knob)
    q, k, v, _ = _qkv(24, 16, seq=32, seed=2)
    main, out = _op_program(24, 16, 32)
    got = pt.Executor().run(
        main, feed={"q": q, "k": k, "v": v},
        fetch_list=[out] + [grad_var_name(n) for n in "qkv"])
    want_out = _composed(q, k, v, True)
    want = jax.grad(lambda q, k, v: jnp.sum(_composed(q, k, v, True) ** 2),
                    (0, 1, 2))(q, k, v)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got,
                          (want_out,) + want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5,
                                   err_msg=name)


def test_per_shard_route_under_a_mesh_takes_a_narrower_value():
    from paddle_tpu.ops.nn_ops import _per_shard_attention
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.mesh import set_mesh
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    set_mesh(None)
    rng = np.random.RandomState(3)
    q, k = (rng.randn(2, 2, 32, 24).astype(np.float32) for _ in range(2))
    v = rng.randn(2, 2, 32, 16).astype(np.float32)

    def attend(q, k, v, mask):
        return flash_attention(q, k, v, mask, causal=True, interpret=True)

    got = _per_shard_attention(attend, mesh, q, k, v, None, "data",
                               "model")
    np.testing.assert_allclose(got, _composed(q, k, v, True), rtol=2e-5,
                               atol=2e-6)
