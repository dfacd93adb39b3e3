"""A decode step's gated delta-rule update as one Pallas TPU kernel.

Every slot's state of one linear-attention layer, ``[slots, d_k, heads *
d_v]`` float32 (ops/delta_ops.py says why it is held that way round), is
read once and written once in place. With ``S' = alpha S`` in VMEM,

    r = S'^T k      p = S'^T q      d = beta (v - r)
    S'' = S' + k d^T               o = p + (k . q) d

``r`` and ``p`` are two sublane reductions of the same block, and ``o``
needs no second pass over what was written: ``S''^T q = S'^T q + (k . q)
d``. As XLA composes the step the decay, the read, the outer product and
the second read are four passes over the state; here a block is in VMEM
between its one read and its one write, and a call's time is the state's
bytes over the memory's bandwidth (chipbench/arith_olmo_hybrid.py counts
them). Float32 on the VPU, as ssm_state_update.py is.

A grid step holds a block of whole heads whose columns fill lane words
(at 192-wide values 2 heads are 384 columns, three words; a block is
``HEADS_A_BLOCK`` of them where the head count allows) and works through
it a lane-aligned GROUP of heads at a time. alpha, beta, v and the
heads' ``k . q`` reach the kernel as rows over the value columns, made
by the caller from the step's projections; a head's key and query as
columns ``[d_k, 1]`` of a small ``[d_k, 2 * heads]`` tile, broadcast
along the lanes of that head's columns.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret_default as _interpret_default
from .kv_cache_append import LANES

# heads a grid step holds, at most: [96, 10 * 192] float32 is 0.74 MB,
# four of them in flight with the pipeline's double buffers
HEADS_A_BLOCK = 10


def group_heads(d_v: int) -> int:
    """The fewest heads whose value columns fill whole lane words."""
    return LANES // math.gcd(d_v, LANES)


def block_heads(heads: int, d_v: int) -> int:
    """Heads a grid step holds: the most whole groups up to
    HEADS_A_BLOCK that divide ``heads`` (a group where none does)."""
    group = group_heads(d_v)
    best = group
    for n in range(group, min(heads, HEADS_A_BLOCK) + 1, group):
        if heads % n == 0:
            best = n
    return best


def fits(state_shape, dtype, heads: int) -> bool:
    """Whether the kernel serves this state: [slots, d_k, heads * d_v]
    float32 with d_k a whole number of sublane tiles and the heads a
    whole number of lane-aligned groups."""
    if len(state_shape) != 3 or jnp.dtype(dtype) != jnp.float32 \
            or heads <= 0 or state_shape[2] % heads:
        return False
    return (state_shape[1] % 8 == 0
            and heads % group_heads(state_shape[2] // heads) == 0)


def _kernel(state_ref, rows_ref, kq_ref, out_ref, o_ref, *, d_v, group,
            block):
    span = group * d_v                   # columns of a group of heads
    col = jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
    for at in range(block // group):     # static: lane-aligned slices
        lanes = pl.ds(at * span, span)
        alpha, beta = rows_ref[0, 0:1, lanes], rows_ref[0, 1:2, lanes]
        v, kq = rows_ref[0, 2:3, lanes], rows_ref[0, 3:4, lanes]

        def columns(first):              # [d_k, span]: a column a head
            out = None
            for i in range(group):
                head = at * group + i
                mine = kq_ref[0, 0, :, first + head:first + head + 1]
                out = mine if out is None else jnp.where(
                    col >= i * d_v, mine, out)
            return out

        k, q = columns(0), columns(block)
        decayed = state_ref[0, :, lanes] * alpha
        r = jnp.sum(decayed * k, axis=0, keepdims=True)
        p = jnp.sum(decayed * q, axis=0, keepdims=True)
        d = beta * (v - r)
        out_ref[0, :, lanes] = decayed + k * d
        o_ref[0, :, lanes] = p + kq * d


# jitted: the layers of a decode program trace and lower ONE kernel
# between them, as ssm_state_update's sites do
@functools.partial(jax.jit, static_argnames=("interpret",))
def _update(state, q, k, v, alpha, beta, *, interpret):
    slots, d_k, columns = state.shape
    heads = q.shape[1]
    d_v = columns // heads
    group, block = group_heads(d_v), block_heads(heads, d_v)
    width = block * d_v

    def by_column(t):                    # [slots, heads] -> [slots, columns]
        return jnp.repeat(t, d_v, axis=1)

    rows = jnp.stack([by_column(alpha), by_column(beta), v,
                      by_column(jnp.sum(k * q, axis=-1))], axis=1)

    def by_block(t):      # [slots, heads, d_k] -> [slots, nb, d_k, block]
        return jnp.swapaxes(t.reshape(slots, heads // block, block, d_k),
                            2, 3)

    kq = jnp.concatenate([by_block(k), by_block(q)], axis=3)
    tile = pl.BlockSpec((1, d_k, width), lambda s, j: (s, 0, j))
    new, o = pl.pallas_call(
        functools.partial(_kernel, d_v=d_v, group=group, block=block),
        grid=(slots, heads // block),
        in_specs=[tile,
                  pl.BlockSpec((1, 4, width), lambda s, j: (s, 0, j)),
                  pl.BlockSpec((1, 1, d_k, 2 * block),
                               lambda s, j: (s, j, 0, 0))],
        out_specs=[tile, pl.BlockSpec((1, 1, width),
                                      lambda s, j: (s, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((slots, 1, columns), jnp.float32)],
        input_output_aliases={0: 0},
        name="delta_state_update",
        interpret=interpret,
    )(state, rows, kq)
    return new, o[:, 0, :]


def delta_state_update(state, q, k, v, alpha, beta, *, interpret=None):
    """(S'', o): ``state`` [slots, d_k, heads * d_v] float32, ``q`` and
    ``k`` [slots, heads, d_k] (normalised, the query scaled), ``v``
    [slots, heads * d_v], ``alpha`` and ``beta`` [slots, heads], all
    float32; o [slots, heads * d_v] float32. Must satisfy ``fits``."""
    if not fits(state.shape, state.dtype, q.shape[1]):
        raise ValueError("delta_state_update kernel cannot serve a state "
                         f"{state.shape} {state.dtype} of {q.shape[1]} "
                         "heads")
    if interpret is None:
        interpret = _interpret_default()
    return _update(state, q, k, v, alpha, beta, interpret=bool(interpret))
