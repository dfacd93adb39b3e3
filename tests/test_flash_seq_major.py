"""The flash kernels on sequence-major arrays (``layout="bshd"``: q
[B, Sq, H, D] read as its free view [B, Sq, H * D], a block the fewest
heads that fill whole 128-lane words), in interpret mode: the output and
all three gradients against the head-major kernels on the transposed
arrays and against a plain float32 ``jax.numpy`` softmax over the whole
score matrix, over 32-, 64- and 128-wide heads (four, two, one a block),
causal and not, a key-row mask, a sequence that is no multiple of the
tile, cross attention (Sq != Sk), grouped key heads, K and V in two
segments, and the shapes the blocks cannot serve, which the entry
transposes and counts ``path="relaid"``. The head-major call's traced
form is pinned too: one ``pallas_call`` a pass, the grid and the block
shapes it had before the kernels learned the second layout. The kernels
compile for the chip in tests/test_tpu_compile.py."""
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


def _reference(q, k, v, mask, causal):
    """Head-major float32 softmax(q k^T / sqrt(d) + mask) v."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if mask is not None:
        s = s + mask
    if causal:
        seen = jnp.arange(q.shape[2])[:, None] >= \
            jnp.arange(k.shape[2])[None, :]
        s = jnp.where(seen, s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def _operands(sq, sk, h, hk, d, dv=None, batch=2, masked=False):
    """Head-major q, k, v, a cotangent and a [B, 1, 1, Sk] key-row mask
    (or None) that hides a different tail of keys in each batch row."""
    dv = d if dv is None else dv
    keys = jax.random.split(jax.random.PRNGKey(sq + 3 * sk + 7 * h + d), 4)
    mask = None
    if masked:
        live = sk - 3 - 5 * jnp.arange(batch)
        mask = jnp.where(jnp.arange(sk)[None, :] < live[:, None], 0.0,
                         -1e9).astype(jnp.float32)[:, None, None, :]
    return (jax.random.normal(keys[0], (batch, h, sq, d)),
            jax.random.normal(keys[1], (batch, hk, sk, d)),
            jax.random.normal(keys[2], (batch, hk, sk, dv)),
            jax.random.normal(keys[3], (batch, h, sq, dv)), mask)


def _turn(x):
    """[B, H, S, D] <-> [B, S, H, D]."""
    return jnp.swapaxes(x, 1, 2)


def _sites(which):
    fam = default_registry().get(f"paddle_tpu_flash_{which}_sites_total")
    return collections.Counter() if fam is None else collections.Counter(
        {labels: child.value for labels, child in fam.samples()})


def _seq_major(q, k, v, mask, causal, **kw):
    """The sequence-major call on head-major operands, its output turned
    back: what a head-major caller would see."""
    return _turn(flash_attention(_turn(q), _turn(k), _turn(v), mask,
                                 causal=causal, interpret=True,
                                 layout="bshd", **kw))


def _check(sq, sk, h, hk, d, causal, masked, label, dv=None, bq=16, bk=16):
    """Output and gradients of the sequence-major call against the
    head-major kernels and the plain reference; the site counters read
    `label` (path, window, group, heads a block; one batch row a block,
    these sequences being several tiles) for the sequence-major
    passes."""
    q, k, v, w, mask = _operands(sq, sk, h, hk, d, dv, masked=masked)
    tiles = dict(block_q=bq, block_k=bk)

    def head_major(q, k, v):
        return flash_attention(q, k, v, mask, causal=causal,
                               interpret=True, **tiles)

    def ours(q, k, v):
        return _seq_major(q, k, v, mask, causal, **tiles)

    def grads(f):
        return jax.grad(lambda *a: jnp.sum(f(*a) * w), (0, 1, 2))(q, k, v)

    want = _reference(q, k, v, mask, causal)
    fwd, bwd = _sites("fwd"), _sites("bwd")
    out = ours(q, k, v)
    got = grads(ours)
    label = (*label, "1")
    assert (_sites("fwd") - fwd) == {label: 2}    # alone, and under grad
    assert (_sites("bwd") - bwd) == {label: 1}
    assert out.shape == want.shape
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    # the same tiles, the same recurrence: the head-major kernels agree
    # to rounding of the order the products sum in
    np.testing.assert_allclose(out, head_major(q, k, v), atol=2e-6,
                               rtol=2e-6)
    ref = grads(lambda *a: _reference(*a, mask, causal))
    for name, a, b, c in zip("qkv", got, ref, grads(head_major)):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg="d" + name)
        np.testing.assert_allclose(a, c, atol=5e-6, rtol=5e-6,
                                   err_msg="d" + name + " (head-major)")


# Sq, Sk, query heads, key heads, head width, causal, key-row mask,
# heads a block
SERVED = [
    pytest.param(48, 48, 4, 4, 64, False, False, 2, id="d64"),
    pytest.param(48, 48, 4, 4, 64, True, False, 2, id="d64-causal"),
    pytest.param(48, 48, 4, 4, 64, False, True, 2, id="d64-key-mask"),
    pytest.param(48, 48, 4, 4, 64, True, True, 2, id="d64-causal-key-mask"),
    pytest.param(48, 48, 8, 8, 32, False, True, 4, id="d32-key-mask"),
    pytest.param(48, 48, 4, 4, 32, True, False, 4, id="d32-causal"),
    pytest.param(48, 48, 3, 3, 128, False, True, 1, id="d128-key-mask"),
    pytest.param(48, 48, 2, 2, 128, True, False, 1, id="d128-causal"),
    pytest.param(72, 72, 2, 2, 64, True, True, 2, id="d64-S-no-tile-multiple"),
    pytest.param(40, 56, 6, 6, 64, False, True, 2, id="d64-cross-Sq-lt-Sk"),
    pytest.param(56, 24, 4, 4, 32, False, False, 4, id="d32-cross-Sq-gt-Sk"),
    pytest.param(48, 48, 6, 2, 128, True, False, 1, id="d128-group-3"),
    pytest.param(40, 56, 4, 1, 128, False, True, 1, id="d128-group-4-cross"),
]


@pytest.mark.parametrize("sq,sk,h,hk,d,causal,masked,heads", SERVED)
def test_sequence_major_matches_head_major_and_a_plain_softmax(
        sq, sk, h, hk, d, causal, masked, heads):
    _check(sq, sk, h, hk, d, causal, masked,
           ("resident", "0", str(h // hk), str(heads)))


# what the sequence-major blocks cannot hold is transposed by the entry
RELAID = [
    pytest.param(48, 3, 3, 64, None, id="odd-head-count"),
    pytest.param(48, 4, 2, 64, None, id="a-group-that-splits-a-block"),
    pytest.param(48, 2, 2, 192, 128, id="192-wide-keys-128-wide-values"),
    pytest.param(48, 2, 2, 24, None, id="a-width-that-cuts-no-word"),
]


@pytest.mark.parametrize("s,h,hk,d,dv", RELAID)
def test_a_shape_the_blocks_cannot_hold_is_relaid(s, h, hk, d, dv):
    _check(s, s, h, hk, d, True, True, ("relaid", "0", str(h // hk), "1"),
           dv=dv)


@pytest.mark.parametrize("kind", ["score", "per-head", "trained"])
def test_a_bias_other_than_a_shared_key_row_is_relaid(kind):
    q, k, v, w, mask = _operands(32, 32, 2, 2, 64, masked=True)
    bias = {"score": jnp.broadcast_to(mask, (2, 1, 32, 32)) * 1.0,
            "per-head": jnp.broadcast_to(mask, (2, 2, 1, 32)) * 1.0,
            "trained": 0.1 * jax.random.normal(jax.random.PRNGKey(5),
                                               (1, 2, 32, 32))}[kind]
    trained = kind == "trained"
    fwd = _sites("fwd")
    out = _seq_major(q, k, v, bias, False, block_q=16, block_k=16,
                     bias_grad=trained)
    assert (_sites("fwd") - fwd) == {("relaid", "0", "1", "1", "1"): 1}
    np.testing.assert_allclose(out, _reference(q, k, v, bias, False),
                               atol=2e-5, rtol=2e-5)
    if trained:     # the bias's gradient comes back through the fallback
        got = jax.grad(lambda b: jnp.sum(_seq_major(
            q, k, v, b, False, block_q=16, block_k=16,
            bias_grad=True) * w))(bias)
        ref = jax.grad(lambda b: jnp.sum(
            _reference(q, k, v, b, False) * w))(bias)
        np.testing.assert_allclose(got, ref, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("d,heads", [(64, 2), (128, 1)])
@pytest.mark.parametrize("causal", [False, True])
def test_k_and_v_in_two_segments(monkeypatch, d, heads, causal):
    """A budget under one head block's K and V: the kernels walk two
    k-segments, the statistics and every head's accumulator waiting in
    scratch between them, dQ leaving as a partial a segment."""
    def fwd_bytes(chunks):
        return fa._fwd_vmem_bytes(chunks, 16, 16, heads * d, heads * d, 4,
                                  128)
    # room for three of the forward's four k-blocks; the backward, which
    # keeps more a key, fits fewer
    monkeypatch.setattr(fa, "_VMEM_BUDGET", fwd_bytes(3))
    assert fwd_bytes(4) > fa._VMEM_BUDGET
    _check(64, 64, 2, 2, d, causal, True,
           ("partial", "0", "1", str(heads)))


def test_sequence_major_windowed_site():
    """A window under the sequence-major blocks: the band's walk is the
    same kernel's."""
    q, k, v, w, _ = _operands(64, 64, 2, 2, 64)
    kw = dict(causal=True, window=20, block_q=16, block_k=16,
              interpret=True)
    want = flash_attention(q, k, v, **kw)
    fwd = _sites("fwd")
    got = _turn(flash_attention(_turn(q), _turn(k), _turn(v),
                                layout="bshd", **kw))
    assert (_sites("fwd") - fwd) == {("resident", "20", "1", "2", "1"): 1}
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)


def test_bf16_operands_and_an_unknown_layout():
    q, k, v, _, mask = _operands(48, 48, 4, 4, 64, masked=True)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    want = flash_attention(q, k, v, mask, causal=True, interpret=True,
                           block_q=16, block_k=16)
    got = _seq_major(q, k, v, mask, True, block_q=16, block_k=16)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), atol=2e-2)
    with pytest.raises(ValueError, match="layout"):
        flash_attention(q, k, v, layout="sbhd", interpret=True)


def _pallas_calls(jaxpr):
    """Every pallas_call equation under a jaxpr, custom_vjp bodies and
    other sub-jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_calls(sub))
    return found


def _blocks_of(eqn):
    mapping = eqn.params["grid_mapping"]
    return mapping.grid, [tuple(int(getattr(d, "block_size", d) or 1)
                                if not isinstance(d, int) else d
                                for d in bm.block_shape)
                          for bm in mapping.block_mappings]


@pytest.mark.parametrize("causal", [False, True])
def test_a_head_major_call_traces_the_calls_it_traced_before(causal):
    """layout="bhsd" (the default): ONE pallas_call a pass, the grid
    (b, h, nq, nseg) and a head's blocks, [1, 1, rows, width] — q, k, v,
    the key-row bias, o and the logsumexp rows forward; q, k, v, bias,
    do, lse, delta, dq, dk, dv backward."""
    b, h, s, d, t = 2, 4, 64, 64, 16
    q, k, v, w, mask = _operands(s, s, h, h, d, batch=b, masked=True)

    def ours(q, k, v):
        return flash_attention(q, k, v, mask, causal=causal,
                               interpret=True, block_q=t, block_k=t)

    fwd = _pallas_calls(jax.make_jaxpr(ours)(q, k, v).jaxpr)
    assert [e.params["name"] for e in fwd] == ["flash_fwd"]
    grid, blocks = _blocks_of(fwd[0])
    nq = s // t
    assert grid == (b, h, nq, 1)
    head, keys = (1, 1, t, d), (1, 1, s, d)
    assert blocks == [head, keys, keys, (1, 1, s, 128), head,
                      (1, 1, 1, 1, t)]

    both = _pallas_calls(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ours(*a) * w), (0, 1, 2)))(q, k, v).jaxpr)
    assert sorted(e.params["name"] for e in both) == [
        "flash_bwd_dkv_dq", "flash_fwd"]
    bwd = [e for e in both if e.params["name"] == "flash_bwd_dkv_dq"][0]
    grid, blocks = _blocks_of(bwd)
    assert grid == (b, h, 1, nq)
    stat = (1, 1, 1, 1, t)
    assert blocks == [head, keys, keys, (1, 1, s, 128), head, stat, stat,
                      (1,) + head, keys, keys]


def test_a_sequence_major_call_reads_head_pairs_in_half_the_grid():
    b, h, s, d, t = 2, 4, 64, 64, 16
    q, k, v, _, mask = _operands(s, s, h, h, d, batch=b, masked=True)
    calls = _pallas_calls(jax.make_jaxpr(
        lambda q, k, v: flash_attention(
            q, k, v, mask, interpret=True, block_q=t, block_k=t,
            layout="bshd"))(_turn(q), _turn(k), _turn(v)).jaxpr)
    assert [e.params["name"] for e in calls] == ["flash_fwd"]
    grid, blocks = _blocks_of(calls[0])
    assert grid == (b, h // 2, s // t, 1)
    pair, keys = (1, t, 2 * d), (1, s, 2 * d)
    assert blocks == [pair, keys, keys, (1, 1, s, 128), pair,
                      (1, 2, 1, 1, t)]


@pytest.mark.parametrize("layout,traces", [("bshd", 1), ("bhsd", 3)])
def test_a_second_sequence_major_site_traces_no_kernel_body(monkeypatch,
                                                            layout, traces):
    """A sequence-major call's ops are traced once a signature
    (_fwd_call_once, _bwd_call_once: jitted with inline=True) and
    inlined at every further site: the kernel bodies run in Python once
    for three sites, the counters count each site. A head-major call is
    traced at every site, as it was: its programs' texts stay what they
    were."""
    q, k, v, w, mask = _operands(32, 32, 2, 2, 64, masked=True)
    if layout == "bshd":
        q, k, v, w = (_turn(x) for x in (q, k, v, w))
    bodies = collections.Counter()
    for name in ("_fwd_kernel", "_bwd_kernel"):
        def counted(*refs, _body=getattr(fa, name), _name=name, **kw):
            bodies[_name] += 1
            return _body(*refs, **kw)
        monkeypatch.setattr(fa, name, counted)

    def three_sites(q, k, v):
        for _ in range(3):
            q = flash_attention(q, k, v, mask, causal=True, interpret=True,
                                block_q=16, block_k=16, layout=layout)
        return jnp.sum(q * w)

    fa._fwd_call_once.clear_cache()
    fa._bwd_call_once.clear_cache()
    fwd, bwd = _sites("fwd"), _sites("bwd")
    got = jax.jit(jax.grad(three_sites, (0, 1, 2)))(q, k, v)
    assert bodies == {"_fwd_kernel": traces, "_bwd_kernel": traces}
    assert sum((_sites("fwd") - fwd).values()) == 3
    assert sum((_sites("bwd") - bwd).values()) == 3
    # traced anew at every site, the same gradients
    monkeypatch.setattr(fa, "_fwd_call_once", fa._fwd_call)
    monkeypatch.setattr(fa, "_bwd_call_once", fa._bwd_call)
    for a, b in zip(got, jax.jit(jax.grad(three_sites, (0, 1, 2)))(q, k, v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
