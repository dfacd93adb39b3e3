"""Decode-step KV-cache append as one Pallas TPU kernel.

The ``kv_cache_append`` op (ops/cache_ops.py) writes one new K or V
row per slot at that slot's own position. As a batched scatter the TPU
compiler runs it as a ``while`` over the slots, one dependent tiny copy
an iteration: 128 serial, latency-bound iterations a cache. Here every
slot is one grid step of ONE kernel whose cache operand is aliased to
its output: a step reads the one (8,128)-tiled block of the cache that
holds the slot's position, replaces the row in it, and stores the
block; Mosaic pipelines the blocks' DMAs across steps. Every other
block of the cache is never touched.

Which block depends on how the DEVICE holds the cache, and the caller
says so with ``lane_axis`` (ops/cache_ops.py reads it from the
backend). The TPU keeps a ``[slots, heads, max_seq, d_key]`` array
row-major (``d_key`` on the 128 lanes) only where ``d_key`` fills
them; a ``d_key`` of 64 is held with the POSITIONS on the lanes and
``d_key`` on the sublanes. A Mosaic call takes its operands row-major,
so the kernel is handed the cache in the device's own order, where the
transpose in and out is a relabelling of the same bytes and not a copy:

  lane_axis 3   cache as is; block (1, heads, R, d_key) at row block
                ``pos // R`` (R the dtype's sublane tile: 8 for 32-bit,
                16 for 16-bit); sublane ``pos % R`` is replaced.
  lane_axis 2   cache as [slots, heads, d_key, max_seq]; block
                (1, heads, d_key, 128) at lane block ``pos // 128``;
                lane ``pos % 128`` is replaced. The new rows come as
                [heads, d_key, slots]: the same sublanes, the slots on
                the lanes, so a lane rotation brings slot s's column
                under the lane it is written to.

Positions land where ``dynamic_update_slice`` and its batched scatter
(mode CLIP) put them: a negative one counts from the end, and the
result is clipped into the cache. The rows written are the bits handed
in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_default as _interpret_default  # shared policy

LANES = 128


def sublane_tile(dtype) -> int:
    """Rows of one (sublane x 128-lane) tile of ``dtype``: 8 at 32
    bits, 16 at 16 bits, 32 at 8."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def fits(cache_shape, dtype, lane_axis) -> bool:
    """Whether the kernel serves this cache: 4-D, a 32- or 16-bit
    float, held with its last or its position axis on the lanes, and
    ``max_seq`` a whole number of the blocks the kernel moves."""
    dtype = jnp.dtype(dtype)
    if len(cache_shape) != 4 or lane_axis not in (2, 3) or not (
            jnp.issubdtype(dtype, jnp.floating)
            and dtype.itemsize in (2, 4)):
        return False
    block = LANES if lane_axis == 2 else sublane_tile(dtype)
    return cache_shape[2] % block == 0


def _row_kernel(pos_ref, cache_ref, new_ref, out_ref, *, rows):
    r = pos_ref[pl.program_id(0)] % rows
    row = jax.lax.broadcasted_iota(jnp.int32, cache_ref.shape, 2)
    out_ref[...] = jnp.where(row == r, new_ref[...], cache_ref[...])


def _lane_kernel(pos_ref, cache_ref, new_ref, out_ref):
    s = pl.program_id(0)
    lane = pos_ref[s] % LANES
    # slot s's column sits at lane s % 128 of its block of new rows
    col = pltpu.roll(new_ref[...], (lane - s % LANES) % LANES, axis=2)
    at = jax.lax.broadcasted_iota(jnp.int32, cache_ref.shape, 3)
    out_ref[...] = jnp.where(at == lane, col[None].astype(out_ref.dtype),
                             cache_ref[...])


# jitted: the K and V sites of every layer of a decode program then
# trace and lower ONE kernel between them (twelve separate lowerings
# cost 0.3 s a program here, 0.75 s of set-up on the chip's host)
@functools.partial(jax.jit, static_argnames=("lane_axis", "interpret"))
def _append(cache, new, pos, *, lane_axis, interpret):
    slots, heads, max_seq, d_key = cache.shape
    pos = pos.astype(jnp.int32)
    pos = jnp.clip(jnp.where(pos < 0, pos + max_seq, pos), 0, max_seq - 1)
    if lane_axis == 3:
        rows = sublane_tile(cache.dtype)
        cache_spec = pl.BlockSpec(
            (1, heads, rows, d_key),
            lambda s, pos: (s, 0, pos[s] // rows, 0))
        new_spec = pl.BlockSpec((1, heads, 1, d_key),
                                lambda s, pos: (s, 0, 0, 0))
        kernel = functools.partial(_row_kernel, rows=rows)
        view = cache
    else:
        cache_spec = pl.BlockSpec(
            (1, heads, d_key, LANES),
            lambda s, pos: (s, 0, 0, pos[s] // LANES))
        new_spec = pl.BlockSpec((heads, d_key, LANES),
                                lambda s, pos: (0, 0, s // LANES))
        kernel = _lane_kernel
        view = jnp.swapaxes(cache, 2, 3)
        # Mosaic rotates 32-bit lanes only: 16-bit rows ride as f32
        # (every bf16 / f16 value is an f32, so the way back is exact)
        new = jnp.transpose(new[:, :, 0, :], (1, 2, 0)).astype(jnp.float32)
        new = jnp.pad(new, ((0, 0), (0, 0), (0, -slots % LANES)))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(slots,),
            in_specs=[cache_spec, new_spec], out_specs=cache_spec),
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        # operand 0 is the prefetched positions; the cache is 1
        input_output_aliases={1: 0},
        name="kv_cache_append",
        interpret=interpret,
    )(pos, view, new)
    return out if lane_axis == 3 else jnp.swapaxes(out, 2, 3)


def kv_cache_append(cache, new, pos, *, lane_axis=3, interpret=None):
    """``cache`` [slots, heads, max_seq, d_key] with row ``pos[s]`` of
    every slot ``s`` replaced by ``new[s]`` ([slots, heads, 1, d_key]);
    ``pos`` [slots] int. ``lane_axis`` is the axis of the cache the
    device holds on its lanes (module docstring); the result is the
    same for either, only what moves differs. The shape must satisfy
    ``fits``."""
    if not fits(cache.shape, cache.dtype, lane_axis):
        raise ValueError(
            f"kv_cache_append kernel cannot serve cache {cache.shape} "
            f"{cache.dtype} with axis {lane_axis} on the lanes")
    if interpret is None:
        interpret = _interpret_default()
    return _append(cache, new.astype(cache.dtype), pos,
                   lane_axis=int(lane_axis), interpret=bool(interpret))
