"""The flash kernels' plan for short sequences (``_rows_a_block``): where
the whole sequence is one q-block against one k-block, a grid step holds
several batch rows of the block and both bodies compute each row's one
tile as values, the rows unrolled. In interpret mode, at the lengths the
dispatcher hands the kernels since the crossover moved (256 and 384), 8
heads x 64 and 4 x 128, a key-row mask, causal and not, both layouts,
one batch row a block (the bodies every longer call traces) and the
plan's own choice (4 rows of a batch of 4, 3 of a batch of 6: the most
up to ``_ROWS_A_STEP`` that divide it): the output and all three
gradients against the plain float32 composition, within the tolerances
tests/test_flash_seq_major.py holds the kernels to. The kernels compile
for the chip in tests/test_tpu_compile.py."""
import collections
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.observability import default_registry
from paddle_tpu.ops.pallas.flash_attention import flash_attention

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def _composition(q, k, v, mask, causal):
    """Head-major float32 softmax(q k^T / sqrt(d) + mask) v."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = s + mask
    if causal:
        seen = jnp.arange(q.shape[2])[:, None] >= \
            jnp.arange(k.shape[2])[None, :]
        s = jnp.where(seen, s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def _operands(b, s, h, d):
    """Head-major q, k, v, a cotangent and a [B, 1, 1, S] key-row mask
    that hides a different tail of keys in each batch row."""
    keys = jax.random.split(jax.random.PRNGKey(b + 3 * s + 7 * h + d), 4)
    live = s - 3 - 5 * jnp.arange(b)
    mask = jnp.where(jnp.arange(s)[None, :] < live[:, None], 0.0,
                     -1e9).astype(jnp.float32)[:, None, None, :]
    return tuple(jax.random.normal(x, (b, h, s, d)) for x in keys) + (mask,)


def _sites(which):
    fam = default_registry().get(f"paddle_tpu_flash_{which}_sites_total")
    return collections.Counter() if fam is None else collections.Counter(
        {labels: child.value for labels, child in fam.samples()})


def _turn(x):
    return jnp.swapaxes(x, 1, 2)


def _check(b, s, h, d, causal, layout, rows, window=None):
    """Output, dq, dk, dv of the kernels against the composition; both
    passes counted with `rows` batch rows a block."""
    q, k, v, w, bias = _operands(b, s, h, d)
    mask = bias
    if window is not None:      # the composition's mask: the band too
        pos = jnp.arange(s)
        mask = bias + jnp.where(
            pos[:, None] - pos[None, :] < window, 0.0, -1e30)
    kw = dict(causal=causal, window=window, interpret=True)

    def ours(q, k, v):
        if layout == "bhsd":
            return flash_attention(q, k, v, bias, **kw)
        return _turn(flash_attention(_turn(q), _turn(k), _turn(v), bias,
                                     layout="bshd", **kw))

    def grads(f):
        return jax.grad(lambda *a: jnp.sum(f(*a) * w), (0, 1, 2))(q, k, v)

    fwd, bwd = _sites("fwd"), _sites("bwd")
    got = grads(ours)
    heads = fa._heads_a_block(d, d) if layout == "bshd" else 1
    label = ("resident", str(window or 0), "1", str(heads), str(rows))
    assert (_sites("fwd") - fwd) == {label: 1}
    assert (_sites("bwd") - bwd) == {label: 1}
    np.testing.assert_allclose(ours(q, k, v),
                               _composition(q, k, v, mask, causal),
                               atol=2e-5, rtol=2e-5)
    want = grads(lambda *a: _composition(*a, mask, causal))
    for name, a, r in zip("qkv", got, want):
        assert a.shape == r.shape, name
        np.testing.assert_allclose(a, r, atol=5e-5, rtol=5e-5,
                                   err_msg="d" + name)


@pytest.mark.parametrize("rows", ["one", "plan"])
@pytest.mark.parametrize("layout", ["bhsd", "bshd"])
@pytest.mark.parametrize("causal", [False, True],
                         ids=["full", "causal"])
@pytest.mark.parametrize("h,d", [(8, 64), (4, 128)],
                         ids=["8x64", "4x128"])
@pytest.mark.parametrize("b,s,plan", [(4, 256, 4), (6, 384, 3)],
                         ids=["4x256", "6x384"])
def test_a_short_site_matches_the_composition(monkeypatch, b, s, plan, h,
                                              d, causal, layout, rows):
    if rows == "one":       # the bodies every longer call traces
        monkeypatch.setattr(fa, "_rows_a_block", lambda *a: 1)
    _check(b, s, h, d, causal, layout, 1 if rows == "one" else plan)


@pytest.mark.parametrize("layout", ["bhsd", "bshd"])
def test_a_window_inside_the_one_tile(layout):
    """Query i sees keys i - 100 < j <= i: both selects in the one
    tile."""
    _check(4, 256, 8, 64, True, layout, 4, window=100)


@pytest.mark.parametrize("layout", ["bhsd", "bshd"])
def test_a_batch_no_row_count_divides_keeps_one_row(layout):
    _check(5, 256, 8, 64, True, layout, 1)


def test_a_budget_under_four_rows_takes_fewer(monkeypatch):
    x = jax.ShapeDtypeStruct((8, 256, 8, 64), jnp.bfloat16)
    site = fa._Site("bshd", x, x, x)
    plan = fa._bwd_plan(site, None, False, True, None, None, False, 2)
    assert plan.rows_a_block == 4
    monkeypatch.setattr(fa, "_VMEM_BUDGET", 2 * (plan.vmem // 4) + 1)
    assert fa._bwd_plan(site, None, False, True, None, None, False,
                        2).rows_a_block == 2


# b, s, heads, width, causal -> batch rows a block
PLANS = [
    pytest.param(8, 2048, 8, 64, False, 1, id="8x2048"),
    pytest.param(8, 2048, 8, 64, True, 1, id="8x2048-causal"),
    pytest.param(1, 8192, 48, 128, True, 1, id="1x8192"),
    pytest.param(64, 1024, 8, 64, True, 1, id="64x1024-causal-two-tiles"),
    pytest.param(1, 512, 32, 64, True, 1, id="a-prefill-of-one-prompt"),
    pytest.param(64, 256, 8, 64, False, 4, id="64x256"),
    pytest.param(64, 256, 8, 64, True, 4, id="64x256-causal"),
    pytest.param(48, 384, 8, 64, False, 4, id="48x384"),
    pytest.param(6, 256, 8, 64, False, 3, id="6x256"),
]


@pytest.mark.parametrize("layout", ["bhsd", "bshd"])
@pytest.mark.parametrize("b,s,h,d,causal,rows", PLANS)
def test_what_a_call_plans_from_its_shapes(b, s, h, d, causal, rows,
                                           layout):
    """Compiled tiles (128-lane units), bf16, a key-row mask: a 2048-long
    call, a causal 1024-long one and a prompt alone keep one batch row a
    block; the short train step's sites take the rows the budget holds,
    the same in both passes."""
    shape = (b, h, s, d) if layout == "bhsd" else (b, s, h, d)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    site = fa._Site(layout, x, x, x)
    mask = jax.ShapeDtypeStruct((b, 1, 1, s), jnp.float32)
    fwd = fa._fwd_plan(site, mask, False, causal, None, None, False, 2)
    bwd = fa._bwd_plan(site, mask, False, causal, None, None, False, 2)
    assert (fwd.rows_a_block, bwd.rows_a_block) == (rows, rows)
    assert fwd.vmem <= fa._VMEM_BUDGET and bwd.vmem <= fa._VMEM_BUDGET


def test_grouped_key_heads_keep_one_row():
    q = jax.ShapeDtypeStruct((4, 8, 256, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((4, 2, 256, 128), jnp.bfloat16)
    site = fa._Site("bhsd", q, k, k)
    assert fa._fwd_plan(site, None, False, True, None, None, False,
                        2).rows_a_block == 1
