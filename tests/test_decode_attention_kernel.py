"""The decode_attention Pallas kernel (ops/pallas/decode_attention.py)
in interpret mode against the attention op's composed rule, the rule's
choice between the two, and the decode programs steered through the
kernel. What the TPU's compiler makes of the kernel is
tests/test_tpu_compile.py's; what the chip runs is chip_smoke.py's."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.ir import OpDesc
from paddle_tpu.core.registry import run_op
from paddle_tpu.observability import default_registry
from paddle_tpu.ops import cache_ops, nn_ops
from paddle_tpu.ops.pallas import decode_attention as kernel
from paddle_tpu.serving.generation import (GenerationConfig,
                                           GenerationModel,
                                           GenerationSpec, bucket_for)

HEADS, D_KEY, MAX_SEQ = 2, 64, 512


def _sdpa_sites():
    fam = default_registry().get("paddle_tpu_sdpa_sites_total")
    if fam is None:
        return collections.Counter()
    return collections.Counter(
        {labels: child.value for labels, child in fam.samples()})


def _rule(q, k, v, kv_len, bound):
    """The registered op's rule, as the executor's trace runs it."""
    op = OpDesc("scaled_dot_product_attention",
                {"Q": ["q"], "K": ["k"], "V": ["v"], "KvLen": ["n"]},
                {"Out": ["o"]}, {"causal": False, "kv_bound": bound})
    extra = {"program": None}   # a site of a step program: counted
    return run_op(op, {"q": q, "k": k, "v": v, "n": kv_len}, extra)["o"]


def _masked_slice(q, k, v, kv_len, bound):
    """What the decode programs built before the op knew lengths: a
    slice to the bucket and a [slots,1,1,L] additive mask."""
    mask = jnp.where(jnp.arange(bound)[None, :] < kv_len[:, None],
                     0.0, -1e9).astype(jnp.float32)[:, None, None, :]
    op = OpDesc("scaled_dot_product_attention",
                {"Q": ["q"], "K": ["k"], "V": ["v"], "Mask": ["m"]},
                {"Out": ["o"]}, {"causal": False})
    return run_op(op, {"q": q, "k": k[:, :, :bound], "v": v[:, :, :bound],
                       "m": mask}, {})["o"]


def _operands(dtype, slots, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(slots, HEADS, 1, D_KEY), jnp.float32)
    k = jnp.asarray(rng.randn(slots, HEADS, MAX_SEQ, D_KEY), dtype)
    v = jnp.asarray(rng.randn(slots, HEADS, MAX_SEQ, D_KEY), dtype)
    return q, k, v


# -- the kernel against the composed rule -----------------------------------

# every length a block edge makes special, at 256-row blocks (bounds
# 256 and 512) and 128-row ones (384): one key, a block's last and the
# next block's first, the bound itself, and an empty slot in between
@pytest.mark.parametrize("bound", [128, 256, 384, 512])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_matches_the_composed_rule_on_ragged_lengths(dtype, bound):
    rows = kernel.block_rows(bound)
    lens = [1, rows - 1, rows, rows + 1, 0, bound - 1, bound, 77]
    lens = np.asarray([n for n in lens if n <= bound])
    q, k, v = _operands(dtype, len(lens), seed=bound)
    kv_len = jnp.asarray(lens, jnp.int64)
    before = _sdpa_sites()
    want = _rule(q, k, v, kv_len, bound)
    assert dict(_sdpa_sites() - before) == \
        {("composed", "kv_len", "0", "0", "1", "bhsd"): 1}  # the CPU's path
    got = kernel.decode_attention(q, k, v, kv_len, bound=bound)
    assert got.shape == q.shape and got.dtype == q.dtype
    live = lens > 0
    # f32 round-off: the sums run in another order, nothing is rounded
    # to 16 bits on the way (a bf16 pass would show 1e-2)
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live],
                               rtol=2e-5, atol=2e-6)
    # a slot of length 0 attends to nothing: zeros, not a mean of junk
    assert not np.asarray(got)[~live].any()


def test_composed_rule_gives_the_bits_of_the_mask_over_a_slice():
    """Off the kernel path the rule slices to the bound and masks from
    KvLen: bit for bit what the decode programs composed before."""
    q, k, v = _operands(jnp.float32, 6)
    kv_len = jnp.asarray([1, 200, 256, 257, 300, 384])
    got = _rule(q, k, v, kv_len, 384)
    want = _masked_slice(q, k, v, kv_len, 384)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lengths_outside_the_bound_are_clipped():
    q, k, v = _operands(jnp.float32, 4)
    got = kernel.decode_attention(q, k, v, jnp.asarray([-3, 999, 256, 5]),
                                  bound=256)
    want = kernel.decode_attention(q, k, v, jnp.asarray([0, 256, 256, 5]),
                                   bound=256)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_only_rows_under_the_length_reach_the_result():
    """Junk past a slot's length changes nothing: in the slot's last
    block a dead key's score is replaced (a NaN there too) and its
    value weighs 0 (finite junk, as on the composed path: a cache row
    holds an earlier request's values), beyond it nothing is read."""
    q, k, v = _operands(jnp.float32, 3)
    lens = np.asarray([5, 256, 300])
    clean = kernel.decode_attention(q, k, v, jnp.asarray(lens), bound=512)
    dead = np.arange(MAX_SEQ)[None, None, :, None] >= \
        lens[:, None, None, None]
    k2 = jnp.where(dead, jnp.nan, k)
    v2 = jnp.where(dead, 1e30, v)
    dirty = kernel.decode_attention(q, k2, v2, jnp.asarray(lens), bound=512)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


@pytest.mark.parametrize("lens,bound,want", [
    ([0, 0, 0], 512, (0, 6)),              # idle slots read nothing
    ([1, 256, 257], 512, (4, 6)),          # 1 + 1 + 2 blocks of 256
    ([1, 128, 129, 384], 384, (7, 12)),    # 384: 128-row blocks
    ([600, -2], 512, (2, 4)),              # clipped to [0, bound]
    ([3, 9], 16, (2, 2)),                  # a bound under one block
])
def test_kv_blocks_counts_what_the_kernel_reads(lens, bound, want):
    assert kernel.kv_blocks(lens, bound) == want
    if bound % kernel.LANES == 0:
        n, slot, block = kernel._work_list(
            jnp.clip(jnp.asarray(lens, jnp.int32), 0, bound), bound,
            kernel.block_rows(bound))
        n = int(n[0])
        assert (n, len(slot)) == want
        pairs = list(zip(np.asarray(slot)[:n].tolist(),
                         np.asarray(block)[:n].tolist()))
        rows = kernel.block_rows(bound)
        assert pairs == [(s, j) for s, length in enumerate(lens)
                         for j in range(-(-min(max(length, 0), bound)
                                          // rows))]


# -- which path the rule takes ----------------------------------------------

class _Ctx:
    def __init__(self, **extra):
        self.extra = extra


@pytest.mark.parametrize("backend,shape,dtype,mesh,q_len,bound,want", [
    ("cpu", (4, 2, 512, 64), jnp.float32, None, 1, 512, None),
    ("tpu", (4, 2, 512, 64), jnp.float32, None, 1, 512, 2),
    ("tpu", (4, 2, 512, 64), jnp.bfloat16, None, 1, 256, 2),
    ("tpu", (4, 2, 512, 64), jnp.float32, None, 1, 24, None),
    ("tpu", (4, 2, 520, 64), jnp.float32, None, 1, 512, None),
    ("tpu", (4, 2, 512, 64), jnp.float32, None, 1, 640, None),
    ("tpu", (4, 2, 512, 64), jnp.int8, None, 1, 512, None),
    ("tpu", (4, 2, 512, 64), jnp.float32, "a mesh", 1, 512, None),
    ("tpu", (4, 2, 512, 64), jnp.float32, None, 3, 512, None),
    ("tpu", (4, 2, 512, 128), jnp.float32, None, 1, 512, None),
], ids=["off_tpu", "f32", "bf16", "bound_not_lane_blocks",
        "seq_not_lane_blocks", "bound_past_cache", "int8", "under_mesh",
        "query_longer_than_1", "row_major_cache"])
def test_rule_takes_the_kernel_only_where_it_can_serve(
        monkeypatch, backend, shape, dtype, mesh, q_len, bound, want):
    """The choice reads the backend, the mesh, the shapes and how the
    device holds the cache, nothing else. (The CPU's layout is
    row-major; the v5e's answers, 2 for a d_key of 64 and 3 for 128,
    are in test_tpu_compile.py and stand in here.)"""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(cache_ops, "device_lane_axis",
                        lambda s, d: 2 if s[3] < 128 else 3)
    q = jax.ShapeDtypeStruct((shape[0], shape[1], q_len, shape[3]),
                             jnp.float32)
    cache = jax.ShapeDtypeStruct(shape, dtype)
    extra = {} if mesh is None else {"mesh": mesh}
    assert nn_ops._decode_kernel_lane_axis(_Ctx(**extra), q, cache,
                                           bound) == want


def test_a_cache_the_kernel_cannot_serve_is_refused_and_composed(
        monkeypatch):
    """On a (pretended) TPU a row-major cache is left to the composed
    path, counted as such; the kernel itself refuses it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q, k, v = _operands(jnp.float32, 3)
    kv_len = jnp.asarray([3, 256, 400])
    before = _sdpa_sites()
    got = _rule(q, k, v, kv_len, 512)   # the CPU answers lane axis 3
    assert dict(_sdpa_sites() - before) == \
        {("composed", "kv_len", "0", "0", "1", "bhsd"): 1}
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(_masked_slice(q, k, v, kv_len, 512)))
    with pytest.raises(ValueError, match="cannot serve"):
        kernel.decode_attention(q, k, v, kv_len, bound=512, lane_axis=3)
    with pytest.raises(ValueError, match="cannot serve"):
        kernel.decode_attention(q, k, v, kv_len, bound=100)


def test_rule_runs_the_kernel_where_it_is_chosen(monkeypatch):
    q, k, v = _operands(jnp.float32, 4)
    kv_len = jnp.asarray([9, 256, 0, 511])
    want = _rule(q, k, v, kv_len, 512)
    monkeypatch.setattr(nn_ops, "_decode_kernel_lane_axis",
                        lambda ctx, q, cache, bound: 2)
    before = _sdpa_sites()
    got = _rule(q, k, v, kv_len, 512)
    assert dict(_sdpa_sites() - before) == \
        {("decode_kernel", "kv_len", "0", "0", "1", "bhsd"): 1}
    live = np.asarray(kv_len) > 0
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live],
                               rtol=2e-5, atol=2e-6)


def test_kv_len_stands_in_for_mask_and_causality():
    q, k, v = _operands(jnp.float32, 2)
    op = OpDesc("scaled_dot_product_attention",
                {"Q": ["q"], "K": ["k"], "V": ["v"], "KvLen": ["n"]},
                {"Out": ["o"]}, {"causal": True, "kv_bound": 256})
    with pytest.raises(ValueError, match="KvLen"):
        run_op(op, {"q": q, "k": k, "v": v, "n": jnp.asarray([1, 2])}, {})


# -- the decode programs through the kernel ---------------------------------

SPEC_KW = dict(vocab_size=50, max_seq_len=256, slots=3,
               prompt_buckets=(8, 128, 256), cache_buckets=(128, 256),
               n_layer=2, n_head=2, d_model=32, d_inner=32, seed=7,
               eos_id=-1)


def _generate_all(model, prompts, mode, max_new_tokens):
    eng = model.serve(config=GenerationConfig(max_new_tokens=max_new_tokens),
                      mode=mode).start()
    try:
        futs = [eng.submit(p) for p in prompts]
        out = [f.result(timeout=300) for f in futs]
    finally:
        eng.stop(drain=True, timeout=300)
    # after the driver thread has joined: it counts a step after the
    # step's tokens have resolved their futures
    return out, eng.stats()


def test_decode_through_the_kernel_matches_composed_and_reforward(
        monkeypatch):
    """The decode programs with their attention steered to the Pallas
    kernel (interpret mode here; on a TPU the rule picks it by itself),
    and the append to its kernel as on the chip, emit the token streams
    of the composed path and of the full re-forward; every attention
    site of a decode program is counted on the path taken; the engine
    counts the blocks read and skipped from its lengths."""
    rng = np.random.RandomState(3)
    # one request leaves the first cache bucket, one stays short and
    # the third slot idles throughout
    prompts = [rng.randint(2, 50, n).tolist() for n in (120, 5)]

    def streams(mode):
        before = _sdpa_sites()
        model = GenerationModel.build(GenerationSpec(**SPEC_KW))
        out, stats = _generate_all(model, prompts, mode, 14)
        sites = collections.Counter()
        for (path, mask, *_rest), n in (_sdpa_sites() - before).items():
            if mask == "kv_len":
                sites[path] += n
        return out, dict(sites), stats, model.spec

    composed, composed_sites, composed_stats, spec = streams("cached")
    reforward, no_sites, _, _ = streams("reforward")
    monkeypatch.setattr(nn_ops, "_decode_kernel_lane_axis",
                        lambda ctx, q, cache, bound: 2)
    monkeypatch.setattr(cache_ops, "_append_kernel_lane_axis",
                        lambda ctx, cache: 2)
    through, kernel_sites, kernel_stats, _ = streams("cached")
    for t, c, r in zip(through, composed, reforward):
        assert t.tokens == c.tokens == r.tokens
        assert t.finish_reason == c.finish_reason == r.finish_reason
    final_len = len(prompts[0]) + len(through[0].tokens)
    entered = {bucket_for(n, spec.cache_buckets)
               for n in range(len(prompts[0]) + 1, final_len + 1)}
    assert entered == {128, 256}
    sites = spec.n_layer * len(entered)
    assert composed_sites == {"composed": sites}
    assert kernel_sites == {"decode_kernel": sites}
    assert no_sites == {}
    # the count is a function of the lengths, the same on either path:
    # both buckets are one block a slot, a request's block is read in
    # every decode step it is live for (one a token after its first)
    # and every other slot's is skipped, the idle third slot's always
    assert kernel.kv_blocks([0] * spec.slots, 128)[1] == spec.slots
    assert kernel.kv_blocks([0] * spec.slots, 256)[1] == spec.slots
    live_steps = sum(len(t.tokens) - 1 for t in through)
    for stats in (kernel_stats, composed_stats):
        blocks = stats["kv_blocks_by_state"]
        assert blocks["read"] == live_steps
        assert blocks["read"] + blocks["skipped"] == \
            spec.slots * stats["steps"]
        assert blocks["skipped"] >= stats["steps"]
