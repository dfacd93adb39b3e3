"""A decode step's recurrent-state update as one Pallas TPU kernel.

Every slot's state of one Mamba-2 layer, ``[slots, d_state, heads *
d_head]`` float32 (ops/ssm_ops.py says why it is held that way round),
is read once and written once in place:

    S' = S * decay + B (dt x)^T          y = S'^T C

``decay`` and ``dt x`` are rows over the heads' columns, made by the
caller from the step's projections; B and C are a slot's two columns
over d_state. As XLA composes it the outer product, the update and the
contraction with C are three passes over the state; here a block of
``[d_state, lane_block]`` is in VMEM between its one read and its one
write, and a decode step's time is the state's bytes over the memory's
bandwidth (chipbench/arith_granite.py counts them).

B and C reach the kernel as ``[d_state, slots]`` arrays, one slot a
lane, whole: a ``[slots, d_state, 1]`` operand would be padded to 128
lanes. The slot's column is picked by a mask and a lane sum, as
decode_attention.py picks a slot's query.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret_default as _interpret_default
from .kv_cache_append import LANES

# columns of the state a grid step holds: [128, 2048] float32 is 1 MB,
# four of them in flight with the pipeline's double buffers
LANE_BLOCK = 2048


def lane_block(columns: int) -> int:
    return LANE_BLOCK if columns % LANE_BLOCK == 0 else LANES


def fits(state_shape, dtype) -> bool:
    """Whether the kernel serves this state: [slots, d_state, columns]
    float32 with d_state a whole number of sublane tiles and the
    columns a whole number of 128-lane blocks."""
    return (len(state_shape) == 3 and jnp.dtype(dtype) == jnp.float32
            and state_shape[1] % 8 == 0 and state_shape[2] % LANES == 0)


def _kernel(state_ref, decay_ref, dx_ref, b_ref, c_ref, out_ref, y_ref):
    s = pl.program_id(0)
    base = pl.multiple_of(s // LANES * LANES, LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    mine = lane == s % LANES

    def column(ref):                                   # [d_state, 1]
        return jnp.sum(jnp.where(mine, ref[:, pl.ds(base, LANES)], 0.0),
                       axis=1, keepdims=True)

    new = state_ref[0] * decay_ref[0] + column(b_ref) * dx_ref[0]
    out_ref[0] = new
    y_ref[0] = jnp.sum(new * column(c_ref), axis=0, keepdims=True)


# jitted: the layers of a decode program trace and lower ONE kernel
# between them, as kv_cache_append's sites do
@functools.partial(jax.jit, static_argnames=("interpret",))
def _update(state, decay, dx, b, c, *, interpret):
    slots, d_state, columns = state.shape
    block = lane_block(columns)
    pad = ((0, 0), (0, -slots % LANES))
    b_t = jnp.pad(b.T, pad)
    c_t = jnp.pad(c.T, pad)
    row = pl.BlockSpec((1, 1, block), lambda s, j: (s, 0, j))
    tile = pl.BlockSpec((1, d_state, block), lambda s, j: (s, 0, j))
    whole = pl.BlockSpec(b_t.shape, lambda s, j: (0, 0))
    new, y = pl.pallas_call(
        _kernel,
        grid=(slots, columns // block),
        in_specs=[tile, row, row, whole, whole],
        out_specs=[tile, row],
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((slots, 1, columns), jnp.float32)],
        input_output_aliases={0: 0},
        name="ssm_state_update",
        interpret=interpret,
    )(state, decay[:, None, :], dx[:, None, :], b_t, c_t)
    return new, y[:, 0, :]


def ssm_state_update(state, decay, dx, b, c, *, interpret=None):
    """(S', y): ``state`` [slots, d_state, columns] float32, ``decay``
    and ``dx`` [slots, columns] float32, ``b`` and ``c`` [slots,
    d_state] float32; y [slots, columns] float32. Must satisfy
    ``fits``."""
    if not fits(state.shape, state.dtype):
        raise ValueError("ssm_state_update kernel cannot serve a state "
                         f"{state.shape} {state.dtype}")
    if interpret is None:
        interpret = _interpret_default()
    return _update(state, decay, dx, b, c, interpret=bool(interpret))
