"""Decode-step attention over the KV cache as one Pallas TPU kernel.

A decode step attends ONE query row a slot to that slot's live prefix
of the cache: keys ``[0, kv_len[s])`` of a ``[slots, heads, max_seq,
d_key]`` cache whose every slot is reserved to ``max_seq``. Composed
from einsum-softmax-einsum over a slice to the step's bucket, XLA reads
the whole slice of every slot — the deepest slot picks the bucket, so a
server whose slots are mostly short reads mostly padding. Here the
device reads, for each slot, only the blocks of ``block_rows`` positions
that hold a live key: the work is ONE flat list of (slot, block) pairs,
built from the lengths before the call, and the kernel is one loop over
that list whose trip count is the number of live blocks. A block past a
slot's length, and every block of a slot of length 0, costs nothing: no
DMA, no loop iteration. Each iteration waits for its K and V block,
has already started the copy of the next pair's (across slots too), and
folds the block into an online softmax; the slot's last block writes
its context.

Everything is f32: the blocks are read in the cache's dtype and widened,
scores are a multiply and a sublane reduction on the VPU (what XLA's
fusion of a one-row product does), softmax and the context accumulate
in f32. Nothing goes through the MXU, so no product is rounded to bf16.

The cache is taken as the DEVICE holds it (ops/cache_ops.py
``device_lane_axis``). A d_key of 64 is held with the positions on the
128 lanes and d_key on the sublanes, and the kernel is handed the
``[slots, heads, d_key, max_seq]`` view of the same bytes, exactly as
kv_cache_append.py is: a block is ``(heads, d_key, block_rows)``, the
query and the context of slot ``s`` are one lane of a ``[heads, d_key,
slots]`` array. A cache held row-major (``lane_axis`` 3, d_key 128)
takes the second body, at the end of this file: products on the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_default as _interpret_default  # shared policy
from .kv_cache_append import LANES, fits as _append_fits, sublane_tile

_NEG = -1e30


def block_rows(bound: int) -> int:
    """Positions a block: the DMA's grain. 256 keeps a 1-MB K+V pair of
    copies in flight (8 heads x 64 x 256 x 4 bytes each) while a short
    slot reads little past its length; a bound that 256 does not divide
    moves 128-lane blocks."""
    return 256 if bound % 256 == 0 else LANES


def fits(cache_shape, dtype, lane_axis, bound) -> bool:
    """Whether a body serves this cache under this bound. Positions on
    the lanes: one the append kernel serves so, d_key whole sublane
    tiles, the bound whole 128-lane blocks inside the cache."""
    if lane_axis == 3:
        return _fits_row_major(cache_shape, dtype, bound)
    return (lane_axis == 2 and _append_fits(cache_shape, dtype, 2)
            and 0 < bound <= cache_shape[2] and bound % LANES == 0
            and cache_shape[3] % sublane_tile(dtype) == 0)


def kv_blocks(lengths, bound):
    """(read, under_bound): the cache blocks one call of the kernel
    reads for these per-slot live lengths, and the blocks a read of
    every slot to the bound would take. One count serves K and V and
    every layer alike. Pure numpy: the engine counts with it."""
    rows = block_rows(bound)
    lengths = np.clip(np.asarray(lengths, np.int64), 0, bound)
    return int((-(-lengths // rows)).sum()), lengths.size * -(-bound // rows)


def _work_list(kv_len, bound, rows):
    """The live (slot, block) pairs in slot order, padded to the static
    worst case, and how many there are."""
    slots = kv_len.shape[0]
    blocks = -(-kv_len // rows)                     # [slots]
    ends = jnp.cumsum(blocks)
    item = jnp.arange(slots * (bound // rows), dtype=jnp.int32)
    # the slot of pair i: how many slots end at or before it (a
    # compare and a sum; searchsorted would loop on the device)
    slot = jnp.minimum(jnp.sum(ends[None, :] <= item[:, None], axis=1),
                       slots - 1).astype(jnp.int32)
    block = item - (ends - blocks)[slot]
    return ends[-1:].astype(jnp.int32), slot, block.astype(jnp.int32)


def _kernel(n_ref, slot_ref, block_ref, len_ref,      # scalar prefetch
            q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sem, m_ref, l_ref, acc_ref, *, rows, scale,
            group=1):
    n_items = n_ref[0]
    key_heads = k_buf.shape[1]
    # the query heads that read key head h are rows g * key_heads + h
    # of q, o and the accumulators, g < group: one pass over the block
    # for each g (one over all rows where a key head has one query head)
    passes = [(slice(None), Ellipsis)] if group == 1 else [
        (slice(g * key_heads, (g + 1) * key_heads),) * 2
        for g in range(group)]

    def copies(item, buf):
        at = (slot_ref[item], slice(None), slice(None),
              pl.ds(pl.multiple_of(block_ref[item] * rows, rows), rows))
        return (pltpu.make_async_copy(k_hbm.at[at], k_buf.at[buf],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[at], v_buf.at[buf],
                                      sem.at[1, buf]))

    def start(item, buf):
        for c in copies(item, buf):
            c.start()

    # a slot of length 0 has no pair in the list: its context is 0
    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_items > 0)
    def _():
        start(0, 0)

    def body(item, carry):
        buf = item % 2

        @pl.when(item + 1 < n_items)
        def _():
            start(item + 1, 1 - buf)

        s, j = slot_ref[item], block_ref[item]
        kv_len = len_ref[s]
        # slot s's query is lane s % 128 of its 128-lane block
        base = pl.multiple_of(s // LANES * LANES, LANES)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, LANES), 2)
        mine = lane == s % LANES
        qs = [jnp.sum(jnp.where(mine, q_ref[hq, :, pl.ds(base, LANES)],
                                0.0), axis=2, keepdims=True)
              for hq, _ in passes]                    # [h, d, 1] each

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, _NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        for c in copies(item, buf):
            c.wait()
        k = k_buf[buf].astype(jnp.float32)            # [h, d, rows]
        v = v_buf[buf].astype(jnp.float32)
        pos = None
        for q, (_, at) in zip(qs, passes):
            scores = jnp.sum(q * k, axis=1, keepdims=True) * scale
            if pos is None:
                pos = j * rows + jax.lax.broadcasted_iota(
                    jnp.int32, (1, 1, rows), 2)
            scores = jnp.where(pos < kv_len, scores, _NEG)  # [h, 1, rows]
            m_prev = m_ref[at]                        # [h, 1, LANES]
            m_new = jnp.maximum(
                m_prev, jnp.max(scores, axis=2, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new[:, :, :1])
            l_ref[at] = alpha * l_ref[at] \
                + jnp.sum(p, axis=2, keepdims=True)
            m_ref[at] = m_new
            pv = p * v                                # [h, d, rows]
            # the lanes are summed once a slot, not once a block
            part = pv[:, :, :LANES]
            for c in range(1, rows // LANES):
                part = part + pv[:, :, c * LANES:(c + 1) * LANES]
            acc_ref[at] = alpha * acc_ref[at] + part

        @pl.when((j + 1) * rows >= kv_len)
        def _():
            ctx = jnp.sum(acc_ref[...], axis=2, keepdims=True) \
                / l_ref[:, :, :1]                     # [h, d, 1]
            at = (slice(None), slice(None), pl.ds(base, LANES))
            o_ref[at] = jnp.where(mine, ctx, o_ref[at])

        return carry

    jax.lax.fori_loop(0, n_items, body, 0)


# jitted: the six sites of a decode program trace and lower ONE kernel
# between them, as kv_cache_append's do
@functools.partial(jax.jit, static_argnames=("bound", "rows", "interpret"))
def _attend(q, k_cache, v_cache, kv_len, *, bound, rows, interpret):
    slots, heads, _, d_key = k_cache.shape
    group = q.shape[1] // heads
    kv_len = jnp.clip(kv_len.astype(jnp.int32), 0, bound)
    n_items, item_slot, item_block = _work_list(kv_len, bound, rows)
    # the query rows as the cache holds its rows: d_key on the
    # sublanes, one slot a lane
    q_rows = q[:, :, 0, :]
    if group != 1:
        # query head h * group + g reads key head h: rows g * heads + h
        q_rows = q_rows.reshape(slots, heads, group, d_key).swapaxes(
            1, 2).reshape(slots, group * heads, d_key)
    q_t = jnp.transpose(q_rows.astype(jnp.float32), (1, 2, 0))
    q_t = jnp.pad(q_t, ((0, 0), (0, 0), (0, -slots % LANES)))
    whole = pl.BlockSpec(q_t.shape, lambda i, *_: (0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, rows=rows,
                          scale=float(1.0 / np.sqrt(d_key)),
                          **({} if group == 1 else {"group": group})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(1,),
            in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((2, heads, d_key, rows), k_cache.dtype),
                pltpu.VMEM((2, heads, d_key, rows), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((group * heads, 1, LANES), jnp.float32),
                pltpu.VMEM((group * heads, 1, LANES), jnp.float32),
                pltpu.VMEM((group * heads, d_key, LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q_t.shape, jnp.float32),
        name="decode_attention",
        interpret=interpret,
    )(n_items, item_slot, item_block, kv_len, q_t,
      jnp.swapaxes(k_cache, 2, 3), jnp.swapaxes(v_cache, 2, 3))
    out = jnp.transpose(out[:, :, :slots], (2, 0, 1))
    if group != 1:
        out = out.reshape(slots, group, heads, -1).swapaxes(1, 2).reshape(
            slots, group * heads, -1)
    return out[:, :, None, :].astype(q.dtype)


def decode_attention(q, k_cache, v_cache, kv_len, *, bound, lane_axis=2,
                     interpret=None):
    """softmax(q k^T / sqrt(d_key)) v over keys ``[0, kv_len[s])`` of slot
    ``s``: ``q`` [slots, group * heads, 1, d_key] (query head h * group + g
    reads key head h), caches [slots, heads, max_seq, d_key] with axis
    ``lane_axis`` on the device's lanes, ``kv_len`` [slots] int clipped to
    ``[0, bound]`` (static); length 0 gets zeros. Must satisfy ``fits``."""
    if k_cache.shape != v_cache.shape or k_cache.dtype != v_cache.dtype \
            or not fits(k_cache.shape, k_cache.dtype, lane_axis, bound):
        raise ValueError(
            f"decode_attention kernel cannot serve caches {k_cache.shape} "
            f"{k_cache.dtype} / {v_cache.shape} {v_cache.dtype} with axis "
            f"{lane_axis} on the lanes under a bound of {bound}")
    interpret = _interpret_default() if interpret is None else interpret
    if lane_axis == 3:
        return _attend_row_major(q, k_cache, v_cache, kv_len,
                                 bound=int(bound), interpret=bool(interpret))
    return _attend(q, k_cache, v_cache, kv_len, bound=int(bound),
                   rows=block_rows(int(bound)), interpret=bool(interpret))


# -- the row-major body -----------------------------------------------------
#
# Below everything the position-minor body is traced from: a Mosaic
# body's bytecode names its file lines, so a line moved above would
# change that kernel's compile-cache key while the kernel is the same.

# Blocks in flight, being multiplied or being folded. On a v5e at this
# PR's cell (2 key heads of 128, 4 query heads each, bfloat16, 256-row
# blocks: 256 KB a pair, 0.31 us of HBM bandwidth) a pair costs, with
# every slot full: 0.66 us at two buffers and the whole pair folded in
# its own trip (a trip waits out the latency of the copy started one
# trip before), 0.57 at three (the row maximum's lane reduction sits
# between the two products), 0.37 / 0.36 at four / five with the NEXT
# pair's scores computed in the trip that folds this one: the copies'
# own time (0.36 with the products left out).
_BUFFERS = 5
# K and V, ``_BUFFERS`` each, of one (heads, rows, d_key) block: what
# the body may hold of the 16 MB a call's VMEM defaults to, beside the
# queries and contexts of every slot
_BLOCK_BYTES = 8 << 20


def _fits_row_major(cache_shape, dtype, bound) -> bool:
    """The row-major case of ``fits``: a cache the append kernel serves
    with d_key on the lanes (4-D, a 32- or 16-bit float), d_key a whole
    number of 128-lane tiles, max_seq and the bound whole numbers of
    128-row blocks, the bound inside the cache, and the blocks in flight
    inside ``_BLOCK_BYTES``."""
    if not _append_fits(cache_shape, dtype, 3):
        return False
    _, heads, max_seq, d_key = cache_shape
    return (d_key % LANES == 0 and max_seq % LANES == 0
            and 0 < bound <= max_seq and bound % LANES == 0
            and 2 * _BUFFERS * heads * block_rows(bound) * d_key
            * jnp.dtype(dtype).itemsize <= _BLOCK_BYTES)


def _kernel_row_major(n_ref, slot_ref, block_ref, len_ref,  # scalar prefetch
                      q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, *,
                      rows, scale):
    """One trip a live (slot, block) pair, as ``_kernel``, over blocks
    ``(heads, rows, d_key)`` as the device holds them. A key head's
    query heads are the rows of two MXU products a block: scores
    ``[group, d_key] x [d_key, rows]`` and context ``[group, rows] x
    [rows, d_key]``, operands at the wider of the queries' and the
    cache's width (bfloat16 against a bfloat16 cache), accumulated in
    float32; scale, length mask, running max, sum and rescale in
    float32; p rounded to the operands' width for the second product:
    the widths of ops/nn_ops.py ``_grouped_cached_attention``.

    A trip folds ITS pair's scores, which the trip before computed, into
    the slot's running max, sum and context, and computes the NEXT
    pair's: the two halves share nothing, so the lane reductions of one
    run under the products of the other. Scores, max, sum and context
    ride the loop as values; every trip writes its slot's context so
    far, and the slot's last block's stands. So that every trip is the
    same straight line, the list is followed by ONE more copy, of its
    last pair's blocks again, which the last trip waits for and
    multiplies and nothing folds."""
    n_items = n_ref[0]
    key_heads, group_rows, d_key = q_ref.shape[1:]
    ahead = _BUFFERS - 1

    def copies(item):
        real = jnp.minimum(item, n_items - 1)
        buf = item % _BUFFERS
        at = (slot_ref[real], slice(None),
              pl.ds(pl.multiple_of(block_ref[real] * rows, rows), rows),
              slice(None))
        return (pltpu.make_async_copy(k_hbm.at[at], k_buf.at[buf],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[at], v_buf.at[buf],
                                      sem.at[1, buf]))

    def start(item):
        for c in copies(item):
            c.start()

    def wait(item):
        for c in copies(item):
            c.wait()

    def scores_of(item):
        """A pair's masked scores and their row maxima, a key head."""
        real = jnp.minimum(item, n_items - 1)
        s, j = slot_ref[real], block_ref[real]
        live = j * rows + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows), 1) < len_ref[s]
        out = []
        for h in range(key_heads):
            q = q_ref[s, h]                           # [group_rows, d]
            k = k_buf[item % _BUFFERS, h].astype(q.dtype)    # [rows, d]
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(live, scores, _NEG)    # [group_rows, rows]
            out.append((scores, jnp.max(scores, axis=1, keepdims=True)))
        return tuple(out)

    # a slot of length 0 has no pair in the list: its context is 0
    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_items > 0)
    def _():
        for item in range(ahead):
            @pl.when(item <= n_items)
            def _(item=item):
                start(item)

        wait(0)

        def body(item, carry):
            stats, pair = carry

            # into the buffers the trip before folded
            @pl.when(item + ahead <= n_items)
            def _():
                start(item + ahead)

            wait(item + 1)
            s, first = slot_ref[item], block_ref[item] == 0
            folded = []
            for h, ((m_prev, l_prev, acc), (scores, top)) in enumerate(
                    zip(stats, pair)):
                v = v_buf[item % _BUFFERS, h].astype(q_ref.dtype)
                # a slot's first block starts its softmax over: alpha
                # is exp(-1e30 - m) = 0 and drops the slot before
                m_prev = jnp.where(first, _NEG, m_prev)
                m_new = jnp.maximum(m_prev, top)
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(scores - m_new)
                l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
                acc = alpha * acc + jnp.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32)
                o_ref[s, h] = acc / l_new
                folded.append((m_new, l_new, acc))
            return tuple(folded), scores_of(item + 1)

        column = jnp.zeros((group_rows, 1), jnp.float32)
        context = jnp.zeros((group_rows, d_key), jnp.float32)
        jax.lax.fori_loop(
            0, n_items, body,
            (((column, column, context),) * key_heads, scores_of(0)))


@functools.partial(jax.jit, static_argnames=("bound", "interpret"))
def _attend_row_major(q, k_cache, v_cache, kv_len, *, bound, interpret):
    slots, heads, _, d_key = k_cache.shape
    group = q.shape[1] // heads
    rows = block_rows(bound)
    kv_len = jnp.clip(kv_len.astype(jnp.int32), 0, bound)
    n_items, item_slot, item_block = _work_list(kv_len, bound, rows)
    # a key head's query heads as the rows of one tile, at the width
    # the composed rule multiplies at
    wide = jnp.result_type(q.dtype, k_cache.dtype)
    q_rows = q[:, :, 0, :].reshape(slots, heads, group, d_key).astype(wide)
    q_rows = jnp.pad(q_rows, ((0, 0), (0, 0),
                              (0, -group % sublane_tile(wide)), (0, 0)))
    whole = pl.BlockSpec(q_rows.shape, lambda i, *_: (0, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel_row_major, rows=rows,
                          scale=float(1.0 / np.sqrt(d_key))),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(1,),
            in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((_BUFFERS, heads, rows, d_key), k_cache.dtype),
                pltpu.VMEM((_BUFFERS, heads, rows, d_key), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, _BUFFERS))]),
        out_shape=jax.ShapeDtypeStruct(q_rows.shape, jnp.float32),
        name="decode_attention_row_major",
        interpret=interpret,
    )(n_items, item_slot, item_block, kv_len, q_rows, k_cache, v_cache)
    return out[:, :, :group].reshape(slots, heads * group, 1, d_key).astype(
        q.dtype)
