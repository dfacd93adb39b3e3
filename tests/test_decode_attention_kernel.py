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

# the kernel's two bodies, by the axis of the cache a v5e holds on its
# lanes: (lane_axis, d_key, query heads a key head). A 64-wide key is
# held position-minor, a 128-wide one row-major, where a key head's
# query heads are the rows of the body's two products
FORMS = [(2, 64, 1), (3, 128, 1), (3, 128, 4)]
FORM_IDS = ["lane_minor", "row_major", "row_major_group4"]


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


def _operands(dtype, slots, seed=0, d_key=D_KEY, group=1,
              q_dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(slots, HEADS * group, 1, d_key), q_dtype)
    k = jnp.asarray(rng.randn(slots, HEADS, MAX_SEQ, d_key), dtype)
    v = jnp.asarray(rng.randn(slots, HEADS, MAX_SEQ, d_key), dtype)
    return q, k, v


# -- the kernel against the composed rule -----------------------------------

# every length a block edge makes special, at 256-row blocks (bounds
# 256 and 512) and 128-row ones (384): one key, a block's last and the
# next block's first, the bound itself, and an empty slot in between
@pytest.mark.parametrize("bound", [128, 256, 384, 512])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("lane_axis,d_key,group", FORMS, ids=FORM_IDS)
def test_kernel_matches_the_composed_rule_on_ragged_lengths(
        lane_axis, d_key, group, dtype, bound):
    rows = kernel.block_rows(bound)
    lens = [1, rows - 1, rows, rows + 1, 0, bound - 1, bound, 77]
    lens = np.asarray([n for n in lens if n <= bound])
    q, k, v = _operands(dtype, len(lens), seed=bound, d_key=d_key,
                        group=group)
    kv_len = jnp.asarray(lens, jnp.int64)
    before = _sdpa_sites()
    want = _rule(q, k, v, kv_len, bound)
    assert dict(_sdpa_sites() - before) == {                # the CPU's path
        ("composed", "kv_len", "0", "0", str(group), "bhsd"): 1}
    got = kernel.decode_attention(q, k, v, kv_len, bound=bound,
                                  lane_axis=lane_axis)
    assert got.shape == q.shape and got.dtype == q.dtype
    live = lens > 0
    # f32 round-off: the sums run in another order, nothing is rounded
    # to 16 bits on the way (a bf16 pass would show 1e-2). Float32
    # queries against a bfloat16 cache multiply at float32 in the
    # composed rule, and so in both bodies
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live],
                               rtol=2e-5, atol=2e-6)
    # a slot of length 0 attends to nothing: zeros, not a mean of junk
    assert not np.asarray(got)[~live].any()


@pytest.mark.parametrize("bound", [256, 512])
@pytest.mark.parametrize("group", [1, 4])
def test_row_major_body_multiplies_at_the_composed_rules_widths(group,
                                                                bound):
    """bfloat16 queries against a bfloat16 cache, as the
    zaya1-8b.serve-reasoning cell's sites: both products take bfloat16
    operands (p rounded to bfloat16 for the second) and accumulate in
    float32, in the kernel as in ``_grouped_cached_attention``. The two
    round p against another maximum (a block's running one, the row's)
    and the context once more to bfloat16: within two roundings of a
    bfloat16 result, 2 x 2**-8, of the rule at the same widths, and no
    farther from the float32 answer than the rule itself is."""
    rows = kernel.block_rows(bound)
    lens = np.asarray([1, rows - 1, rows, rows + 1, bound - 1, bound, 77])
    q, k, v = _operands(jnp.bfloat16, len(lens), seed=bound, d_key=128,
                        group=group, q_dtype=jnp.bfloat16)
    kv_len = jnp.asarray(lens)
    # XLA's CPU backend multiplies no bfloat16 x bfloat16 -> float32
    # batch inside a program; op by op it does
    want = np.asarray(_rule(q, k, v, kv_len, bound), np.float32)
    got = kernel.decode_attention(q, k, v, kv_len, bound=bound, lane_axis=3)
    assert got.dtype == jnp.bfloat16
    got = np.asarray(got, np.float32)
    np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2 ** -9)
    exact = np.asarray(_rule(*(x.astype(jnp.float32) for x in (q, k, v)),
                             kv_len, bound))
    assert np.abs(got - exact).max() <= 1.5 * np.abs(want - exact).max()


def test_composed_rule_gives_the_bits_of_the_mask_over_a_slice():
    """Off the kernel path the rule slices to the bound and masks from
    KvLen: bit for bit what the decode programs composed before."""
    q, k, v = _operands(jnp.float32, 6)
    kv_len = jnp.asarray([1, 200, 256, 257, 300, 384])
    got = _rule(q, k, v, kv_len, 384)
    want = _masked_slice(q, k, v, kv_len, 384)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("lane_axis,d_key,group", FORMS, ids=FORM_IDS)
def test_lengths_outside_the_bound_are_clipped(lane_axis, d_key, group):
    q, k, v = _operands(jnp.float32, 4, d_key=d_key, group=group)
    got = kernel.decode_attention(q, k, v, jnp.asarray([-3, 999, 256, 5]),
                                  bound=256, lane_axis=lane_axis)
    want = kernel.decode_attention(q, k, v, jnp.asarray([0, 256, 256, 5]),
                                   bound=256, lane_axis=lane_axis)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("lane_axis,d_key,group", FORMS, ids=FORM_IDS)
def test_only_rows_under_the_length_reach_the_result(lane_axis, d_key,
                                                     group, dtype):
    """Junk past a slot's length changes nothing: in the slot's last
    block a dead key's score is replaced (a NaN there too) and its
    value weighs 0 (finite junk, as on the composed path: a cache row
    holds an earlier request's values), beyond it nothing is read."""
    q, k, v = _operands(dtype, 3, d_key=d_key, group=group, q_dtype=dtype)
    lens = np.asarray([5, 256, 300])
    clean = kernel.decode_attention(q, k, v, jnp.asarray(lens), bound=512,
                                    lane_axis=lane_axis)
    dead = np.arange(MAX_SEQ)[None, None, :, None] >= \
        lens[:, None, None, None]
    k2 = jnp.where(dead, jnp.nan, k)
    v2 = jnp.where(dead, 1e30, v)
    dirty = kernel.decode_attention(q, k2, v2, jnp.asarray(lens), bound=512,
                                    lane_axis=lane_axis)
    np.testing.assert_array_equal(np.asarray(clean, np.float32),
                                  np.asarray(dirty, np.float32))


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
    ("tpu", (4, 2, 512, 128), jnp.float32, None, 1, 512, 3),
    ("tpu", (96, 2, 2048, 128), jnp.bfloat16, None, 1, 1024, 3),
    ("tpu", (4, 2, 512, 256), jnp.bfloat16, None, 1, 384, 3),
    ("cpu", (4, 2, 512, 128), jnp.bfloat16, None, 1, 512, None),
    ("tpu", (4, 2, 512, 192), jnp.bfloat16, None, 1, 512, None),
    ("tpu", (4, 2, 512, 128), jnp.bfloat16, None, 1, 200, None),
    ("tpu", (4, 2, 520, 128), jnp.bfloat16, None, 1, 512, None),
    ("tpu", (4, 2, 512, 128), jnp.bfloat16, None, 1, 640, None),
    ("tpu", (4, 2, 512, 128), jnp.bfloat16, "a mesh", 1, 512, None),
    ("tpu", (4, 2, 512, 128), jnp.bfloat16, None, 3, 512, None),
    ("tpu", (4, 2, 512, 128), jnp.int8, None, 1, 512, None),
    ("tpu", (4, 64, 512, 128), jnp.float32, None, 1, 512, None),
], ids=["off_tpu", "f32", "bf16", "bound_not_lane_blocks",
        "seq_not_lane_blocks", "bound_past_cache", "int8", "under_mesh",
        "query_longer_than_1", "row_major_cache", "row_major_cell",
        "row_major_two_lane_tiles", "row_major_off_tpu",
        "row_major_d_key_not_a_lane_tile", "row_major_bound_not_blocks",
        "row_major_seq_not_blocks", "row_major_bound_past_cache",
        "row_major_under_mesh", "row_major_query_longer_than_1",
        "row_major_int8", "row_major_blocks_past_vmem"])
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
    """On a (pretended) TPU a row-major cache whose keys are no whole
    128-lane tile (the CPU holds every cache row-major, this one at a
    d_key of 64) is left to the composed path, counted as such; the
    kernel itself refuses it."""
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


@pytest.mark.parametrize("lane_axis,d_key,group", FORMS, ids=FORM_IDS)
def test_rule_runs_the_kernel_where_it_is_chosen(monkeypatch, lane_axis,
                                                 d_key, group):
    q, k, v = _operands(jnp.float32, 4, d_key=d_key, group=group)
    kv_len = jnp.asarray([9, 256, 0, 511])
    want = _rule(q, k, v, kv_len, 512)
    monkeypatch.setattr(nn_ops, "_decode_kernel_lane_axis",
                        lambda ctx, q, cache, bound: lane_axis)
    before = _sdpa_sites()
    got = _rule(q, k, v, kv_len, 512)
    assert dict(_sdpa_sites() - before) == {
        ("decode_kernel", "kv_len", "0", "0", str(group), "bhsd"): 1}
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


@pytest.mark.parametrize("lane_axis,d_model", [(2, 32), (3, 256)],
                         ids=["lane_minor", "row_major"])
def test_decode_through_the_kernel_matches_composed_and_reforward(
        monkeypatch, lane_axis, d_model):
    """The decode programs with their attention steered to the Pallas
    kernel (interpret mode here; on a TPU the rule picks it by itself,
    the position-minor body for two heads of 16, the row-major one for
    two of 128), and the append to its kernel as on the chip, emit the
    token streams of the composed path and of the full re-forward; every
    attention site of a decode program is counted on the path taken; the
    engine counts the blocks read and skipped from its lengths."""
    rng = np.random.RandomState(3)
    # one request leaves the first cache bucket, one stays short and
    # the third slot idles throughout
    prompts = [rng.randint(2, 50, n).tolist() for n in (120, 5)]

    def streams(mode):
        before = _sdpa_sites()
        model = GenerationModel.build(
            GenerationSpec(**dict(SPEC_KW, d_model=d_model)))
        out, stats = _generate_all(model, prompts, mode, 14)
        sites = collections.Counter()
        for (path, mask, *_rest), n in (_sdpa_sites() - before).items():
            if mask == "kv_len":
                sites[path] += n
        return out, dict(sites), stats, model.spec

    composed, composed_sites, composed_stats, spec = streams("cached")
    reforward, no_sites, _, _ = streams("reforward")
    monkeypatch.setattr(nn_ops, "_decode_kernel_lane_axis",
                        lambda ctx, q, cache, bound: lane_axis)
    monkeypatch.setattr(cache_ops, "_append_kernel_lane_axis",
                        lambda ctx, cache: lane_axis)
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
