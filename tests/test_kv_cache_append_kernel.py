"""The kv_cache_append Pallas kernel (ops/pallas/kv_cache_append.py) in
interpret mode against the op's batched-scatter rule, bit for bit, and
the rule's choice between the two. What the TPU's compiler makes of the
kernel is tests/test_tpu_compile.py's; what the chip runs is
chip_smoke.py's."""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.ir import OpDesc
from paddle_tpu.core.registry import run_op
from paddle_tpu.observability import default_registry
from paddle_tpu.ops import cache_ops
from paddle_tpu.ops.pallas import kv_cache_append as kernel

HEADS, MAX_SEQ = 2, 256


def _site_counts():
    fam = default_registry().get("paddle_tpu_kv_append_sites_total")
    if fam is None:
        return collections.Counter()
    return collections.Counter(
        {labels[0]: child.value for labels, child in fam.samples()})


def _rule(cache, new, pos):
    """The registered op's rule, as the executor's trace runs it."""
    op = OpDesc("kv_cache_append",
                {"Cache": ["c"], "New": ["n"], "Pos": ["p"]},
                {"Out": ["c"]}, {})
    extra = {"program": None}   # a site of a step program: counted
    return run_op(op, {"c": cache, "n": new, "p": pos}, extra)["c"]


def _bits(x):
    return np.asarray(jax.lax.bitcast_convert_type(
        x, jnp.uint32 if x.dtype.itemsize == 4 else jnp.uint16))


def _positions(kind, slots, rows, rng):
    if kind == "random":
        return rng.randint(0, MAX_SEQ, slots)
    if kind == "one_position":         # every slot at the same row
        return np.full(slots, 77)
    if kind == "inactive_at_0":        # the engine parks idle slots at 0
        pos = rng.randint(1, MAX_SEQ, slots)
        pos[::2] = 0
        return pos
    if kind == "out_of_range":         # clipped as the scatter clips
        return np.resize([MAX_SEQ, MAX_SEQ + 9, -1, -MAX_SEQ - 3], slots)
    return np.full(slots, {"first": 0, "tile_end": rows - 1,
                           "tile_start": rows, "lane_block": 128,
                           "last": MAX_SEQ - 1}[kind])


def _case(dtype, slots, d_key, lane_axis, kind):
    rng = np.random.RandomState(slots * 1000 + d_key)
    cache = jnp.asarray(rng.randn(slots, HEADS, MAX_SEQ, d_key), dtype)
    new = jnp.asarray(rng.randn(slots, HEADS, 1, d_key), dtype)
    pos = jnp.asarray(_positions(kind, slots, kernel.sublane_tile(dtype),
                                 rng), jnp.int64)
    before = _site_counts()
    want = _rule(cache, new, pos)
    assert _site_counts() - before == {"scatter": 1}   # the CPU's path
    got = kernel.kv_cache_append(cache, new, pos, lane_axis=lane_axis)
    assert got.dtype == cache.dtype and got.shape == cache.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))
    # one row a slot is new, every other row is the cache's own bits
    changed = (_bits(got) != _bits(cache)).any(axis=(1, 3))
    assert (changed.sum(axis=1) <= 1).all()


KINDS = ["first", "tile_end", "tile_start", "lane_block", "last",
         "out_of_range", "one_position", "inactive_at_0"]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("lane_axis", [2, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_writes_the_scatters_bits_at_each_position(dtype, lane_axis,
                                                          kind):
    _case(dtype, 4, 64, lane_axis, kind)


@pytest.mark.parametrize("d_key", [64, 128])
@pytest.mark.parametrize("slots", [1, 4, 128])
@pytest.mark.parametrize("lane_axis", [2, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_writes_the_scatters_bits_at_each_size(dtype, lane_axis,
                                                      slots, d_key):
    _case(dtype, slots, d_key, lane_axis, "random")


def test_negative_zero_and_nan_rows_keep_their_bits():
    """The lane form moves a row by rotation and select, never through
    arithmetic: -0.0 stays -0.0 and a NaN keeps its payload."""
    cache = jnp.zeros((4, HEADS, MAX_SEQ, 64), jnp.float32)
    raw = np.array([0x80000000, 0x7FC00001, 0xFFC12345, 0x00000001],
                   np.uint32)
    new = jnp.asarray(np.resize(raw, (4, HEADS, 1, 64)).view(np.float32))
    pos = jnp.asarray([3, 130, 255, 0])
    for lane_axis in (2, 3):
        got = kernel.kv_cache_append(cache, new, pos, lane_axis=lane_axis)
        np.testing.assert_array_equal(_bits(got), _bits(_rule(cache, new,
                                                              pos)))


# -- which path the rule takes ----------------------------------------------

class _Ctx:
    def __init__(self, **extra):
        self.extra = extra


@pytest.mark.parametrize("backend,shape,dtype,mesh,want", [
    ("cpu", (4, 2, 256, 64), jnp.float32, None, None),     # no TPU
    ("tpu", (4, 2, 256, 64), jnp.float32, None, 3),        # served
    ("tpu", (4, 2, 256, 64), jnp.bfloat16, None, 3),
    ("tpu", (4, 2, 20, 64), jnp.float32, None, None),      # 20 % 8 != 0
    ("tpu", (4, 2, 24, 64), jnp.bfloat16, None, None),     # 24 % 16 != 0
    ("tpu", (4, 2, 256, 64), jnp.int8, None, None),        # not a float
    ("tpu", (4, 2, 256, 64), jnp.float32, "a mesh", None),  # GSPMD
    ("tpu", (8, 256, 64), jnp.float32, None, None),        # not 4-D
], ids=["off_tpu", "f32", "bf16", "seq_not_tiled_f32", "seq_not_tiled_bf16",
        "int8", "under_mesh", "rank3"])
def test_rule_takes_the_kernel_only_where_it_can_serve(
        monkeypatch, backend, shape, dtype, mesh, want):
    """The choice reads the backend, the mesh and the shape and nothing
    else. (The CPU's default layout is row-major, so the axis it
    answers here is 3; the v5e's answers are in test_tpu_compile.py.)"""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cache = jax.ShapeDtypeStruct(shape, dtype)
    extra = {} if mesh is None else {"mesh": mesh}
    assert cache_ops._append_kernel_lane_axis(_Ctx(**extra), cache) == want


def test_a_cache_the_kernel_cannot_serve_takes_the_scatter(monkeypatch):
    """On a (pretended) TPU backend a max_seq of 20 is not a whole
    number of 8-row tiles: the rule runs the scatter, counts it as
    such, and the kernel itself refuses the shape."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rng = np.random.RandomState(0)
    cache = jnp.asarray(rng.randn(4, 2, 20, 16), jnp.float32)
    new = jnp.asarray(rng.randn(4, 2, 1, 16), jnp.float32)
    pos = jnp.asarray([0, 7, 19, 25])
    before = _site_counts()
    got = _rule(cache, new, pos)
    assert _site_counts() - before == {"scatter": 1}
    want = np.asarray(cache).copy()
    for s, p in enumerate([0, 7, 19, 19]):
        want[s, :, p, :] = np.asarray(new)[s, :, 0, :]
    np.testing.assert_array_equal(np.asarray(got), want)
    with pytest.raises(ValueError, match="cannot serve"):
        kernel.kv_cache_append(cache, new, pos, lane_axis=3)


def test_rule_runs_the_kernel_where_it_is_chosen(monkeypatch):
    """With the choice steered to the kernel (interpret mode off the
    TPU) the rule's output is the scatter's, and the site is counted
    as a kernel site."""
    rng = np.random.RandomState(1)
    cache = jnp.asarray(rng.randn(4, 2, 256, 16), jnp.float32)
    new = jnp.asarray(rng.randn(4, 2, 1, 16), jnp.float32)
    pos = jnp.asarray([0, 130, 255, 9])
    want = _rule(cache, new, pos)
    for lane_axis in (2, 3):
        monkeypatch.setattr(cache_ops, "_append_kernel_lane_axis",
                            lambda ctx, c, _a=lane_axis: _a)
        before = _site_counts()
        got = _rule(cache, new, pos)
        assert _site_counts() - before == {"kernel": 1}
        np.testing.assert_array_equal(_bits(got), _bits(want))


# -- donation ---------------------------------------------------------------

@pytest.mark.parametrize("lane_axis", [2, 3])
def test_donated_cache_is_aliased_to_the_output(lane_axis):
    """A jitted call that donates the cache compiles, on the CPU too,
    to a module whose cache parameter is aliased to its output."""
    cache = jax.ShapeDtypeStruct((4, 2, 256, 64), jnp.float32)
    new = jax.ShapeDtypeStruct((4, 2, 1, 64), jnp.float32)
    pos = jax.ShapeDtypeStruct((4,), jnp.int32)
    text = jax.jit(
        lambda c, n, p: kernel.kv_cache_append(c, n, p,
                                               lane_axis=lane_axis),
        donate_argnums=0).lower(cache, new, pos).compile().as_text()
    header = text[:text.find("\n\n")]
    assert re.search(r"input_output_alias=\{\s*\{\}: \(0, \{\}, "
                     r"(may|must)-alias\)", header), header[:400]
