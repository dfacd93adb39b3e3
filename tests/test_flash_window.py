"""The flash kernels under a window and under grouped key heads
(ops/pallas/flash_attention.py), in interpret mode against a masked
softmax over the whole score matrix in ``jax.numpy`` with K and V
repeated to the query heads: the output and all three gradients, over
windows smaller than, equal to and larger than a tile, a window and a
sequence that are no multiple of the tile, unequal tiles, groups of 1,
6 and 8 query heads a key head, 64- and 128-wide heads. The kernels
compile for the chip at the benchmark's shapes in
tests/test_tpu_compile.py."""
import collections
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.observability import default_registry
from paddle_tpu.ops.pallas.flash_attention import flash_attention

# the package exports the function under the module's name
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def _reference(q, k, v, window):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i = jnp.arange(q.shape[2])[:, None]
    j = jnp.arange(k.shape[2])[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)


def _operands(s, h, hk, d, batch=1):
    keys = jax.random.split(jax.random.PRNGKey(s + 7 * h + d), 4)
    return (jax.random.normal(keys[0], (batch, h, s, d)),
            jax.random.normal(keys[1], (batch, hk, s, d)),
            jax.random.normal(keys[2], (batch, hk, s, d)),
            jax.random.normal(keys[3], (batch, h, s, d)))


def _sites(which):
    fam = default_registry().get(f"paddle_tpu_flash_{which}_sites_total")
    return collections.Counter() if fam is None else collections.Counter(
        {labels: child.value for labels, child in fam.samples()})


# S, query heads, key heads, head width, window, block_q, block_k
CASES = [
    pytest.param(64, 2, 2, 64, 8, 16, 16, id="window-under-a-tile"),
    pytest.param(64, 6, 1, 64, 16, 16, 16, id="window-a-tile-group-6"),
    pytest.param(64, 8, 1, 128, 32, 16, 16, id="window-two-tiles-group-8"),
    pytest.param(64, 6, 1, 128, 20, 16, 16, id="window-no-tile-multiple"),
    pytest.param(72, 8, 1, 64, 20, 16, 16, id="S-no-tile-multiple"),
    pytest.param(72, 4, 2, 64, 40, 32, 16, id="q-tile-twice-the-k-tile"),
    pytest.param(72, 4, 2, 64, 5, 32, 8, id="window-under-a-small-k-tile"),
    pytest.param(72, 4, 2, 64, 40, 16, 32, id="k-tile-twice-the-q-tile"),
    pytest.param(64, 6, 1, 64, None, 16, 16, id="causal-group-6"),
    pytest.param(100, 8, 2, 128, None, 32, 16, id="causal-group-4-ragged"),
    pytest.param(40, 2, 1, 64, 12, None, None, id="one-default-tile"),
]


@pytest.mark.parametrize("s,h,hk,d,window,bq,bk", CASES)
def test_output_and_gradients_match_a_masked_softmax(s, h, hk, d, window,
                                                     bq, bk):
    q, k, v, w = _operands(s, h, hk, d)

    def ours(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=bq, block_k=bk, interpret=True)

    fwd, bwd = _sites("fwd"), _sites("bwd")
    out = ours(q, k, v)
    want = _reference(q, k, v, window)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * w), (0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: jnp.sum(_reference(*a, window) * w),
                   (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, ref):
        assert a.shape == b.shape, name      # dK, dV at the key heads
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg="d" + name)
    # one head and (a group walks one dK / dV accumulator) one batch row
    # a block
    label = ("resident", str(window or 0), str(h // hk), "1", "1")
    assert (_sites("fwd") - fwd)[label] == 2      # alone, and under grad
    assert (_sites("bwd") - bwd)[label] == 1


def test_a_window_that_reaches_every_key_is_plain_causal_bit_for_bit():
    q, k, v, _ = _operands(48, 4, 2, 64)
    plain = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                            interpret=True)
    for window in (48, 49, 4096):
        wide = flash_attention(q, k, v, causal=True, window=window,
                               block_q=16, block_k=16, interpret=True)
        np.testing.assert_array_equal(np.asarray(wide), np.asarray(plain))
    # one key short of all of them is a window: the first key leaves the
    # last query's sight
    short = flash_attention(q, k, v, causal=True, window=47, block_q=16,
                            block_k=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(short[:, :, :47]),
                                  np.asarray(plain[:, :, :47]))
    assert np.abs(np.asarray(short[:, :, 47] - plain[:, :, 47])).max() > 0


def test_windowed_calls_are_named_and_read_k_and_v_at_their_own_heads(
        monkeypatch):
    q, k, v, _ = _operands(64, 6, 1, 64)
    calls, real = [], fa.pl.pallas_call

    def spy(*a, **kw):
        call = real(*a, **kw)

        def run(*args):
            calls.append((kw["name"], [x.shape for x in args]))
            return call(*args)
        return run

    monkeypatch.setattr(fa.pl, "pallas_call", spy)
    for window, suffix in ((16, "_window"), (None, "")):
        del calls[:]
        jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=True, window=window, block_q=16, block_k=16,
            interpret=True)), (0, 1, 2))(q, k, v)
        assert [name for name, _ in calls] == [
            "flash_fwd" + suffix, "flash_bwd_dkv_dq" + suffix]
        for _, shapes in calls:
            # q at the six query heads; K and V enter at their own one
            # head: no copy of them at the query heads is made for it
            assert shapes[:3] == [(1, 6, 64, 64), (1, 1, 64, 64),
                                  (1, 1, 64, 64)]


@pytest.mark.parametrize("s,window,bq,bk", [
    (256, 32, 32, 32), (256, 48, 32, 16), (256, 20, 16, 32),
    (8192, 512, 512, 512), (4096, 512, 512, 512), (200, 7, 24, 8),
])
def test_the_walk_is_the_band_and_its_edge_tiles(s, window, bq, bk):
    """For every q-block, [start, stop) holds exactly the k-blocks with
    a visible pair, [start, edge) exactly those the window's lower edge
    crosses, and the walk is ceil((W + block_q) / block_k) blocks at
    most whatever S."""
    q_of = np.arange(s)[:, None]
    k_of = np.arange(s)[None, :]
    seen = (k_of <= q_of) & (k_of > q_of - window)
    too_old = k_of <= q_of - window
    longest = 0
    for iq in range(-(-s // bq)):
        rows = slice(iq * bq, min((iq + 1) * bq, s))
        stop = min(-(-s // bk), (iq * bq + bq - 1) // bk + 1)
        start, edge = (int(x) for x in fa._band_edges(
            iq, 0, stop, bq, bk, window))
        live = [c for c in range(-(-s // bk))
                if seen[rows, c * bk:(c + 1) * bk].any()]
        assert live == list(range(start, min(stop, live[-1] + 1))), iq
        crossed = [c for c in live
                   if too_old[rows, c * bk:(c + 1) * bk].any()]
        assert crossed == list(range(start, edge)), iq
        longest = max(longest, stop - start)
    assert longest <= -(-(window + bq) // bk) + (bq % bk != 0)
    if bq % bk == 0 and s >= 2 * (window + bq):
        assert longest == -(-(window - 1) // bk) + bq // bk


def test_what_the_kernels_do_not_take_raises():
    q, k, v, _ = _operands(32, 4, 2, 64)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=False, window=8, interpret=True)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=0, interpret=True)
    with pytest.raises(ValueError, match="query heads"):
        flash_attention(q[:, :3], k, v, causal=True, interpret=True)
    with pytest.raises(ValueError, match="query heads"):
        flash_attention(q, k, v[:, :1], causal=True, interpret=True)
