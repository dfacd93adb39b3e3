"""Flash attention as Pallas TPU kernels (forward + backward).

Online-softmax tiled attention: O(S) memory instead of the O(S^2) scores
matrix of the naive composition (reference composes attention from
matmul/softmax in python/paddle/fluid/nets.py:312; its hand-fused CUDA
analogue for recurrent hot loops is paddle/cuda/src/hl_cuda_lstm.cu —
Pallas is the TPU-native equivalent of that hand-fusion layer).

Layout "bhsd" (head-major): q [B, H, Sq, D], k [B, Hk, Sk, D], v [B, Hk,
Sk, Dv] (Dv may differ from D: latent attention keeps 192-wide keys
beside 128-wide values; the scale stays Q's. Hk divides H: grouped-query
attention, query head h reads key head h // (H / Hk) through the K and V
BlockSpecs, and no H-head copy of K or V exists), out [B, H, Sq, Dv],
optional additive bias/mask broadcastable as [B, {1|H}, Sq, Sk].

Layout "bshd" (sequence-major): q [B, Sq, H, D], k [B, Sk, Hk, D], v [B,
Sk, Hk, Dv], out [B, Sq, H, Dv] — the reshape of what a projection
writes and reads, [B, S, H * D], in which head h of a row lies in lanes
h * D .. (h + 1) * D. The SAME two kernels read that view through other
BlockSpecs, `(1, rows, 128-lane block)` at (batch, row-block, block of
heads): a block is the fewest heads that fill whole 128-lane words (two
at D = 64, four at 32, one where D and Dv are multiples of 128), the
grid's head axis counts blocks, and a grid step runs the tile
recurrence once a head of its block (a static loop in _fwd_kernel, a
fori_loop in _bwd_kernel: their docstrings say why). No array is
transposed on either side of a site, a resident K / V row is 128 dense
lanes, o's stores are unmasked. What the blocks cannot hold
(_seq_major_serves) the entry transposes and runs head-major, counted
path="relaid". A head-major call traces exactly the kernels it traced
before the second layout existed.

Both passes have one shape. A head's K and V stay resident in VMEM, a
grid step is one q-block, and an in-kernel loop walks the k-blocks: up
to the diagonal on a causal site, never past the last key, and under a
`window` (query i sees keys i - window < j <= i) from the first block
the window reaches, so a windowed site walks at most
ceil((window + block_q) / block_k) k-blocks a q-block whatever S (one
more where block_k does not divide block_q). A score tile is held
keys-down, [block_k, block_q], so what belongs to a query — the
running max and sum, the logsumexp, delta — is a [1, block_q] row that
reduces down sublanes and broadcasts along them. How much of K and V
stays resident is a byte count against _VMEM_BUDGET: a whole head where
it fits (every site of the benchmark's cells), else the fewest equal
k-segments, walked by the same kernel. Where the whole sequence is ONE
tile each way (a site of 256-511 rows: the lengths the crossover below
hands these kernels first) a grid step would be a microsecond of work,
so a step holds several batch rows of the block (_rows_a_block) and
both bodies compute each row's one tile as values, the rows unrolled;
every longer call keeps one row a step and traces the kernels it always
traced.

The forward (grid (batch, head, q-block, k-segment)) carries the
statistics between tiles as values and keeps the accumulator turned,
[dv, block_q], in scratch; o is turned back once a q-block and the
logsumexp leaves compact, [B, H, Sq] f32. The masks run where they bite:
the key-padding select only where the keys do not fill their last
block, the causal select only in the tiles the diagonal crosses, the
window's only in the tiles its lower edge crosses.

The backward (grid (batch, head, k-segment, q-block)) is ONE more kernel
on the logsumexp residual — the flash-attention-2 recurrence with each
score tile's s, p, dp and ds computed once and feeding dV, dK and dQ (a
dq and a dkv kernel would each recompute them); f32 dK and dV
accumulators sit beside the resident K and V and dQ leaves finished, or,
a segment at a time, as an f32 partial a segment for XLA to sum. Under
grouped queries the grid's head axis is the KEY head and its last axis
walks the group's query heads, each q-block by q-block, so dK and dV
leave summed over the group, at Hk heads. An
exact additive-bias gradient is emitted from the same tiles on request.
"""
from __future__ import annotations

import collections
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


from . import interpret_default as _interpret_default  # shared policy


# The shortest sequence a dispatcher that was left the choice hands to
# these kernels (ops/nn_ops.py _sdpa: q AND k at least this long;
# parallel/context_parallel.py: the gathered q). Measured in PR 51 on a
# TPU v5e (16 GB, JAX 0.9.0), bf16, 8 heads x 64, a key-row mask,
# layout "bshd", forward + backward, 16-18 k tokens a site. One site
# alone (`_cmp/pr51_site.py`: 40 dependent sites in one jit, ms a site,
# kernels / composition): 64 x 256 0.825 / 1.092 (causal 0.806 / 1.096),
# 48 x 384 1.065 / 1.758 (1.074 / 1.801), 32 x 512 1.104 / 2.494 (0.976
# / 2.489) — the kernels win 1.3x, 1.65x and 2.3-2.6x. At the step's
# level (transformer-base, batch 64 x 256, 18 sites; chipbench's
# `train-s256`, 40-s windows) the composition pays its score tensors'
# layout copies too (8 % of busy time under no program op): 176.2 k
# tokens/s composed, 209.9 k through the kernels as PR 50 left them
# (where one site alone read 1.162 against 1.092: the step, not the site
# alone, decides), 229.8 k under the short-sequence plan (P C C P, two
# seeds: PERF.md section 6). NOT below 256 without a measurement of its
# own: the token server's 128-token prefill bucket (forward only, one
# prompt) takes the composition, and `ttft_ms_p95` is its metric. The
# 512 this replaces was a round-3 reading (the composition 1.56x faster
# at 256, parity at 512) of kernels that have since become 3.4x faster.
FLASH_CROSSOVER_SEQ = 256

# What one call may plan to keep in VMEM: half of a v5e core's 128 MiB,
# the rest being Mosaic's own. A call asks for what it counted
# (vmem_limit_bytes), not for the default scoped 16 MiB.
_VMEM_BUDGET = 64 << 20

# The most batch rows a grid step holds under the short-sequence plan
# (_rows_a_block has the measurement: 4 rows read within 1 % of 8, whose
# unrolled bodies take up to twice as long to compile — 0.8 -> 1.3 and
# 1.2 -> 2.4 s a forward and backward pair; 16 read worse).
_ROWS_A_STEP = 4


def _blocks(sq, sk, block_q, block_k, interpret):
    """A pass's tiles under its caps: each axis split into the fewest
    tiles that respect its cap, the tile shrunk to fit (1100 under a cap
    of 1024: two tiles of 640, not of 1024). A score tile is held
    keys-down [block_k, block_q] and K and V are sliced by rows of
    block_k, so compiled tiles are whole 128-lane columns one way and
    whole packed sublane tiles the other."""
    unit = 8 if interpret else 128

    def fit(s, cap):
        n = -(-s // max(cap, unit))
        return _ceil_to(-(-s // n), unit)

    return fit(sq, block_q), fit(sk, block_k)


def _segments(nk, vmem_bytes):
    """How much of a head's K and V stays resident is a byte count: all
    `nk` k-blocks where that fits the budget, else the fewest equal
    segments that do. Returns (segments, k-blocks a segment)."""
    chunks = nk
    while chunks > 1 and vmem_bytes(chunks) > _VMEM_BUDGET:
        chunks -= 1
    nseg = -(-nk // chunks)
    return nseg, -(-nk // nseg)


def _bias_kind(bias):
    """What a bias costs a tile: a "key" row [.., 1, Sk|1] is one value
    a key, a "score"-sized one [.., Sq, Sk|1] a block of the tile's
    size."""
    if bias is None:
        return None
    return "key" if bias.shape[2] == 1 else "score"


def _bias_lanes(bias, block_q):
    """Lanes a key's bias values fill as the keys-down kernels read
    them (_keys_down_bias)."""
    return {None: 0, "key": 128, "score": block_q}[_bias_kind(bias)]


def _keys_down_bias(bias, sq, sk, sq_p, sk_p, seg, block_q, where,
                    rows_a_block=1):
    """A bias as the keys-down kernels read it, broadcast dims of batch
    and head kept unmaterialized: a key row as one value a key on every
    lane, [.., sk_p, 128]; a score-sized one turned, [.., sk_p, sq_p].
    `where` picks (query head, k-segment, q-block) out of the grid's
    last three indices. Returns the array and its BlockSpec, a block the
    `rows_a_block` batch rows of a grid step where the bias has a row a
    batch row."""
    bb, bh = bias.shape[:2]
    pad_k = ((0, 0), (0, 0), (0, sk_p - sk))
    key = _bias_kind(bias) == "key"
    if key:
        biasp = jnp.broadcast_to(
            jnp.pad(jnp.broadcast_to(bias[:, :, 0], (bb, bh, sk)),
                    pad_k)[..., None], (bb, bh, sk_p, 128))
    else:
        biasp = jnp.pad(
            jnp.swapaxes(jnp.broadcast_to(bias, (bb, bh, sq, sk)), 2, 3),
            pad_k + ((0, sq_p - sq),))

    def at(b, h, i, j):
        head, ks, iq = where(h, i, j)
        return (0 if bb == 1 else b, 0 if bh == 1 else head, ks,
                0 if key else iq)

    return biasp, pl.BlockSpec(
        (1 if bb == 1 else rows_a_block, 1, seg,
         _bias_lanes(bias, block_q)), at)


def _caps(bias, bias_grad, causal, block_q, block_k):
    """The tile caps of both passes (an explicit block_q / block_k always
    wins), from what the call can see. A trained bias takes 128: its
    gradient drifts with the tile (~4e-3 rel between 128 and 512 at
    S=1024, fp32 reassociation). A score-sized bias hands every tile a
    block of its own size and keeps 512. A key-row mask is one column
    of a tile and caps nothing: such a site, like a bias-free one,
    takes 1024, or 512 where it is causal, half of a tile on the
    diagonal being masked work. Measured on a v5e, ms a site, tiles of
    512 / 1024 — forward (PR 40; the grid-step-a-tile kernel before it
    2.71 and 2.05 at the first two, 2.47 at the third): 64 heads x 2048
    x 64 bf16 under a key-row mask 1.20 / 1.10 not causal, 0.91 / 0.95
    causal; 32 heads x 4096 x 192 / 128 causal, no bias 2.10 / 2.16.
    Backward (PR 38): 2.50 / 2.42, 1.81 / 1.99, 4.19 / 4.42."""
    if bias_grad and bias is not None:
        cap = 128
    elif _bias_kind(bias) == "score" or causal:
        cap = 512
    else:
        cap = 1024
    return (cap if block_q is None else block_q,
            cap if block_k is None else block_k)


def _band_edges(iq, first, stop, block_q, block_k, window):
    """Where a q-block's walk under a window starts and where the
    window's lower edge stops crossing tiles, in k-blocks of this
    segment: key j is visible to query i iff i - window < j <= i, so
    the first query's oldest key lies in block `start`, and from block
    `edge` on every key of a block is young enough for the q-block's
    last query. Both within [0, stop]."""
    oldest = jnp.maximum(iq * block_q - (window - 1), 0)
    start = jnp.clip(oldest // block_k - first, 0, stop)
    whole_from = jnp.maximum(iq * block_q + block_q - window, 0)
    edge = jnp.clip((whole_from + block_k - 1) // block_k - first,
                    start, stop)
    return start, edge


def _walk_band(tile, carry, iq, first, stop, block_q, block_k, window):
    """A windowed q-block's walk over its band alone: the tiles the
    window's lower edge crosses take both selects (in the forward a row
    that sees no key of such a tile is put right by its next tile's
    alpha = exp(-1e30 - m) = 0), the whole tiles none, the tiles the
    diagonal crosses the causal one."""
    start, lo = _band_edges(iq, first, stop, block_q, block_k, window)
    whole = jnp.clip((iq * block_q + 1) // block_k - first, lo, stop)
    carry = jax.lax.fori_loop(
        start, lo, functools.partial(tile, diagonal=True, lower=True), carry)
    carry = jax.lax.fori_loop(
        lo, whole, functools.partial(tile, diagonal=False), carry)
    return jax.lax.fori_loop(
        whole, stop, functools.partial(tile, diagonal=True), carry)


def _visible(s, qpos, kpos, window):
    """The causal select of a score tile, and the window's with it."""
    seen = qpos >= kpos
    if window is not None:
        seen = seen & (qpos - kpos < window)
    return jnp.where(seen, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _lanes(width):
    return _ceil_to(width, 128)


def _fwd_vmem_bytes(chunks, block_q, block_k, d, dv, itemsize, bias_lanes):
    """Bytes a forward call keeps in VMEM with `chunks` k-blocks of one
    head resident: K and V (a row fills whole 128-lane words) and the
    bias rows, double-buffered by the pipeline; one q-block's q and o
    double-buffered, the f32 accumulator and its turned copy, the
    statistics' rows; the f32 score-sized values of a tile in flight."""
    per_key = 2 * itemsize * (_lanes(d) + _lanes(dv)) + 8 * bias_lanes
    per_step = block_q * (2 * itemsize * (_lanes(d) + _lanes(dv))
                          + 8 * _lanes(dv) + 256)
    return (chunks * block_k * per_key + per_step
            + 4 * 4 * block_q * block_k)


def _head_lanes(x, i, heads, other=0):
    """`x` [rows, heads * width] with every lane but head i's zeroed (or
    taken from `other`): a product that contracts over the block's lanes
    then yields head i's alone, and one whose output keeps the lanes
    leaves the other heads' zero. A block of one head is handed back as
    it is; `i` may be a loop's index (the backward's)."""
    if heads == 1:
        return x
    width = x.shape[-1] // heads
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where((lane >= i * width) & (lane < (i + 1) * width), x,
                     jnp.asarray(other, x.dtype))




def _each_row(body, at, rows_a_block):
    """`body(at)` once a batch row of a grid step's block: `at` as it is
    where the block holds one row; else the row leads `at`, the index of
    a loop that is traced once and unrolled whole, so that the rows lie
    in one block of straight-line code."""
    if rows_a_block == 1:
        body(at)
    else:
        jax.lax.fori_loop(0, rows_a_block,
                          lambda r, _: body((r, *at[1:])), None,
                          unroll=True)


def _fwd_kernel(*refs, sm_scale, scale_q, causal, window, block_q, block_k,
                kv_len, chunks, nseg, bias_kind, heads, at, rows_a_block):
    """One q-block against the `chunks` k-blocks of one resident
    k-segment, scores held keys-down ([block_k, block_q]): the running
    max and sum reduce down sublanes and travel between tiles as
    [1, block_q] rows, the accumulator is [dv, block_q] and is turned
    once a q-block. Across the segments of a head too long to stay
    resident the rows and the accumulator wait in scratch.

    `at` leads a block's rows and lanes: (0, 0) on a head-major array,
    (0,) on a sequence-major one, whose block holds `heads` heads side
    by side in its lanes. The recurrence then runs once a head: the
    scores from q with the other heads' lanes zeroed (the contraction
    runs over the block's whole lanes and k is read as it lies), the
    values by a static lane slice of v (under a head's p the block's
    whole values would stream twice the rows: v5e, ms a site of 64 heads
    x 2048 x 64, 1.07 against 0.91 not causal, 0.82 against 0.71
    causal; k sliced too reads the same as zeroed q, 0.89 and 0.72),
    each head's statistics in its own row of the scratch and its
    accumulator in its own dv rows of [heads * dv, block_q], so that ONE
    turn a q-block writes o's dense lanes. The heads are a static loop:
    a lane slice needs a head known at trace time, and a branch a head
    inside a tile costs what the whole values cost (1.05 and 0.85 ms).

    Under the short-sequence plan (_rows_a_block: the whole sequence ONE
    tile) a block holds `rows_a_block` > 1 batch rows, the row the head
    of `at` (_each_row); a head of a row is then computed as values —
    s, its max, p, its sum, v^T p, all over the one tile, bit for bit
    what the walk above gives — with no scratch and no `pl.when`, so
    that the rows' products and softmaxes overlap."""
    q_ref, k_ref, v_ref = refs[:3]
    bias_ref = refs[3] if bias_kind is not None else None
    o_ref, lse_ref, m_scr, l_scr, acc_scr = refs[-5:]
    iq, ks = pl.program_id(2), pl.program_id(3)
    dv = v_ref.shape[-1] // heads
    one_tile = rows_a_block > 1
    if one_tile:
        iq = 0

    def _row(at):
        # the bias's row: this batch row's, or the one all share
        brow = at[0] if bias_kind and bias_ref.shape[0] > 1 else 0

        if not one_tile:
            @pl.when(ks == 0)
            def _init():
                m_scr[:] = jnp.full_like(m_scr, NEG_INF)
                l_scr[:] = jnp.zeros_like(l_scr)
                acc_scr[:] = jnp.zeros_like(acc_scr)

        block = q_ref[at]                                    # [bq, d]
        if scale_q:       # a power of two: the scaled scores bit for bit
            block = block * sm_scale
        first = ks * chunks if nseg > 1 else 0
        # k-blocks past the last key, or wholly above the causal
        # diagonal: nothing to do
        stop = -(-kv_len // block_k) - first
        stop = (min(chunks, stop) if nseg == 1
                else jnp.minimum(chunks, stop))
        if causal:
            stop = jnp.minimum(
                stop, (iq * block_q + block_q - 1) // block_k + 1 - first)

        def _head(i):
            # this head's row of the statistics; its lanes of v, which
            # are its rows of the accumulator
            row = slice(None) if heads == 1 else slice(i, i + 1)
            own = (slice(None) if heads == 1
                   else slice(i * dv, (i + 1) * dv))
            q = _head_lanes(block, i, heads)

            def _scores(c, diagonal, lower=False):
                """Tile c's scores [bk, bq] and its values."""
                rows = pl.ds(pl.multiple_of(c * block_k, block_k),
                             block_k)
                k = k_ref[(*at, rows, slice(None))]
                v = v_ref[(*at, rows, own)]
                s = jax.lax.dot_general(
                    k, q, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)      # [bk, bq]
                if not scale_q:
                    s = s * sm_scale
                if bias_kind == "key":      # one value a key, on every lane
                    s = s + bias_ref[brow, 0, rows, :][:, :1].astype(
                        jnp.float32)
                elif bias_kind == "score":
                    s = s + bias_ref[brow, 0, rows, :].astype(jnp.float32)
                if kv_len % block_k or diagonal:
                    kpos = (first + c) * block_k \
                        + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                if kv_len % block_k:        # mask seq padding
                    s = jnp.where(kpos < kv_len, s, NEG_INF)
                if diagonal:
                    qpos = iq * block_q + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 1)
                    s = _visible(s, qpos, kpos, window if lower else None)
                return s, v

            if one_tile:
                # the whole sequence: no statistic to carry, no
                # accumulator to rescale, nothing waits in scratch
                s, v = _scores(0, causal, window is not None)
                m = jnp.max(s, axis=0, keepdims=True)        # [1, bq]
                p = jnp.exp(s - m)
                l = jnp.sum(p, axis=0, keepdims=True)
                acc = jax.lax.dot_general(
                    v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)      # [dv, bq]
                lse_ref[at[0], i, 0] = m + jnp.log(jnp.maximum(l, 1e-37))
                return acc / jnp.where(l == 0.0, 1.0, l)

            def _tile(c, carry, diagonal, lower=False):
                m, l = carry                                 # [1, bq]
                s, v = _scores(c, diagonal, lower)
                m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)                       # [bk, bq]
                l = alpha * l + jnp.sum(p, axis=0, keepdims=True)
                acc_scr[own] = acc_scr[own] * alpha + jax.lax.dot_general(
                    v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)      # [dv, bq]
                return m_new, l

            carry = m_scr[row], l_scr[row]
            if window is not None:
                m, l = _walk_band(_tile, carry, iq, first, stop, block_q,
                                  block_k, window)
            else:
                # the causal select only in the tiles the diagonal
                # crosses: the whole tiles below it first
                whole = 0
                if causal:
                    whole = jnp.clip(
                        (iq * block_q + 1) // block_k - first, 0, stop)
                    carry = jax.lax.fori_loop(
                        0, whole, functools.partial(_tile, diagonal=False),
                        carry)
                # a short walk of a known length is unrolled, so that
                # the next tile's products overlap this tile's softmax
                # (v5e, a site of 64 heads x 2048 x 64 not causal: 1.10
                # against 1.16 ms)
                m, l = jax.lax.fori_loop(
                    whole, stop, functools.partial(_tile, diagonal=causal),
                    carry,
                    unroll=True if isinstance(stop, int) and stop <= 4
                    else None)
            m_scr[row], l_scr[row] = m, l

            @pl.when(ks == nseg - 1)
            def _fin():
                # fully-masked rows -> 0
                safe = jnp.where(l == 0.0, 1.0, l)
                if heads == 1:
                    o_ref[at] = (acc_scr[:] / safe).T.astype(o_ref.dtype)
                else:
                    acc_scr[own] = acc_scr[own] / safe
                lse_ref[at[0], i, 0] = m + jnp.log(jnp.maximum(l, 1e-37))

        if one_tile:
            o_ref[at] = jnp.concatenate(
                [_head(i) for i in range(heads)]).T.astype(o_ref.dtype)
            return
        for i in range(heads):      # static: see the docstring
            _head(i)
        if heads > 1:
            @pl.when(ks == nseg - 1)
            def _turn():
                o_ref[at] = acc_scr[:].T.astype(o_ref.dtype)

    _each_row(_row, at, rows_a_block)


class _Site:
    """A call's arrays as its layout holds them, read once by both
    passes. Head-major ("bhsd", and "relaid": a sequence-major call the
    entry transposed) q is [B, H, Sq, D] and a block one head's rows,
    `(1, 1, rows, width)` at (batch, head, row-block, 0). Sequence-major
    ("bshd") q is [B, Sq, H, D] read as its free view [B, Sq, H * D] and
    a block the rows of `heads` heads side by side in whole 128-lane
    words, `(1, rows, heads * width)` at (batch, row-block, head-block).
    `h` and `hk` count head BLOCKS, the kernels' grid axis. Either
    way a block is `rows_a_block` batch rows of that (_rows_a_block: one
    but under the short-sequence plan) and the grid's first axis counts
    such blocks."""

    def __init__(self, layout, q, k, v, rows_a_block=1):
        self.rows_a_block = rows_a_block
        self.seq_major = layout == "bshd"
        if self.seq_major:
            self.b, self.sq, h, self.d = q.shape
            self.sk, hk, self.dv = k.shape[1], k.shape[2], v.shape[3]
            self.heads = _heads_a_block(self.d, self.dv)
        else:
            self.b, h, self.sq, self.d = q.shape
            hk, self.sk, self.dv = k.shape[1], k.shape[2], v.shape[3]
            self.heads = 1
        self.group = h // hk
        self.h, self.hk = h // self.heads, hk // self.heads
        # what leads a block's rows and lanes inside the kernels
        self.at = (0,) if self.seq_major else (0, 0)
        self.relaid = layout == "relaid"

    def rows(self, x, pad):
        """`x` as the kernels read it, its rows padded by `pad`."""
        if self.seq_major:
            return jnp.pad(x.reshape(*x.shape[:2], -1),
                           ((0, 0), (0, pad), (0, 0)))
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))

    def shape(self, blocks, rows, width):
        """Of an output of `blocks` head blocks a batch."""
        if self.seq_major:
            return self.b, rows, blocks * self.heads * width
        return self.b, blocks, rows, width

    def spec(self, rows, width, where, lead=None):
        """The BlockSpec of `rows` rows of one head block; `where` maps
        the grid's indices to (batch, head block, row-block), `lead` to
        the index of a leading axis the array has besides (dQ's partial
        a k-segment)."""
        if self.seq_major:
            block = (self.rows_a_block, rows, self.heads * width)

            def index(*grid):
                b, h, r = where(*grid)
                return b, r, h
        else:
            block = (self.rows_a_block, 1, rows, width)

            def index(*grid):
                return (*where(*grid), 0)
        if lead is None:
            return pl.BlockSpec(block, index)
        return pl.BlockSpec((1,) + block,
                            lambda *grid: (lead(*grid), *index(*grid)))

    def stat_spec(self, block_q, where):
        """Of the logsumexp and delta rows [B, H, nq, 1, block_q]: a
        block's heads, one row each."""
        return pl.BlockSpec(
            (self.rows_a_block, self.heads, 1, 1, block_q),
            lambda *grid: (*where(*grid), 0, 0))

    def unview(self, x, rows, width):
        """An output's first `rows` rows, in the layout's 4-D form."""
        if self.seq_major:
            return x[:, :rows].reshape(self.b, rows, -1, width)
        return x[:, :, :rows]


def _heads_a_block(d, dv):
    """Heads a sequence-major block holds: the fewest that fill whole
    128-lane words — 128 // d where the one width of keys and values
    divides 128, one where both are multiples of 128, None where the
    lanes cannot be cut that way."""
    if d % 128 == 0 and dv % 128 == 0:
        return 1
    if d == dv and 128 % d == 0:
        return 128 // d
    return None


# A call's tiles and segments: block_q, block_k, q-blocks, k-segments,
# k-blocks a segment, batch rows a block, the bytes it keeps in VMEM.
_Plan = collections.namedtuple(
    "_Plan", "block_q block_k nq nseg chunks rows_a_block vmem")


def _rows_a_block(site, nq, nk, vmem_a_row):
    """Batch rows a grid step holds: the short-sequence plan. One
    wherever a head's sequence is more than one tile (every call of 1024
    or longer, 512 causal), which then traces the kernels it always
    traced. Where the whole sequence is ONE q-block against ONE k-block
    nothing overlaps inside a head's walk — a product, then its softmax,
    then the next product, through scratch and `pl.when`s that a single
    tile does not need — and a grid step is a microsecond of that. Such
    a call takes the most rows up to _ROWS_A_STEP that divide the batch
    (and fit the budget, counted a row as the plan counts a call), and
    with more than one row the bodies change form: each row's heads are
    computed as values, no scratch, no conditional, in one unrolled
    block, so that one row's products run under another's softmax.
    Grouped key heads keep one row: the backward's last grid axis walks
    the group over one dK / dV accumulator. Measured on a v5e (PR 51,
    `_cmp/pr51_site.py`: ms a site of 64 x 256 x 8 heads x 64 bf16 under
    a key-row mask, forward + backward, 40 dependent sites in one jit,
    0.15 of it the scan's own passes): one row through scratch 1.166
    (causal 1.214); 8 or 16 such rows a step in a loop 1.139 / 1.193 —
    the fixed cost of a grid step is NOT what a short site pays; one row
    as values 0.935 (0.920); 2 / 4 / 8 / 16 rows as values, unrolled,
    0.844 / 0.825 / 0.820 / 0.875 (causal 0.825 / 0.806 / 0.799 /
    0.855). 48 x 384: 1.309 -> 1.065; 32 x 512: 1.156 -> 1.104, causal
    1.241 -> 0.976. The composition: 1.092, 1.758, 2.494."""
    if nq * nk > 1 or site.group > 1:
        return 1
    most = max(1, min(_ROWS_A_STEP, _VMEM_BUDGET // vmem_a_row))
    return max(r for r in range(1, most + 1) if site.b % r == 0)


def _fwd_plan(site, bias, bias_grad, causal, block_q, block_k, interpret,
              itemsize):
    """A forward call's tiles and segments (_Plan), from its shapes
    alone."""
    block_q, block_k = _blocks(
        site.sq, site.sk, *_caps(bias, bias_grad, causal, block_q, block_k),
        interpret)
    nq, nk = -(-site.sq // block_q), -(-site.sk // block_k)

    def vmem_bytes(chunks):
        return _fwd_vmem_bytes(chunks, block_q, block_k,
                               site.heads * site.d, site.heads * site.dv,
                               itemsize, _bias_lanes(bias, block_q))

    nseg, chunks = _segments(nk, vmem_bytes)
    rows = _rows_a_block(site, nq, nk, vmem_bytes(chunks))
    return _Plan(block_q, block_k, nq, nseg, chunks, rows,
                 rows * vmem_bytes(chunks))


def _fwd(q, k, v, bias, sm_scale, causal, window, block_q, block_k,
         interpret, bias_grad, layout="bhsd"):
    """o as q's layout holds it and the logsumexp [B, H, Sq] f32. Counts
    the site, then _fwd_call. A sequence-major call's ops — the kernel's
    body above all, 40-90 ms of Python a site, and a two-head body holds
    every op twice — are traced ONCE a signature (_fwd_call_once) and
    inlined wherever a site of that signature is traced again (18 sites
    of two signatures in transformer-base's step). A head-major call is
    traced at every site as it always was: a shared trace shares its
    lowered private functions too, jax numbers a module's functions in
    the order it first lowers them, and every head-major program's text
    would change by those numbers."""
    site = _Site(layout, q, k, v)
    plan = _fwd_plan(site, bias, bias_grad, causal, block_q, block_k,
                     interpret, q.dtype.itemsize)
    _count_site("fwd", plan, window, site)
    call = _fwd_call_once if site.seq_major else _fwd_call
    return call(q, k, v, bias, sm_scale, causal, window, interpret, layout,
                plan)


def _fwd_call(q, k, v, bias, sm_scale, causal, window, interpret, layout,
              plan):
    block_q, block_k, nq, nseg, chunks, rows_a_block, vmem = plan
    site = _Site(layout, q, k, v, rows_a_block)
    b, h, sq, sk, d, dv = site.b, site.h, site.sq, site.sk, site.d, site.dv
    group, heads = site.group, site.heads
    seg = chunks * block_k
    sq_p, sk_p = nq * block_q, nseg * seg

    qp = site.rows(q, sq_p - sq)
    kp, vp = site.rows(k, sk_p - sk), site.rows(v, sk_p - sk)

    def qspec(width):
        return site.spec(block_q, width, lambda b, h, iq, ks: (b, h, iq))

    def kspec(width):        # a group's query heads read one key head
        return site.spec(
            seg, width,
            lambda b, h, iq, ks: (b, h if group == 1 else h // group, ks))

    in_specs = [qspec(d), kspec(d), kspec(dv)]
    args = [qp, kp, vp]
    if bias is not None:
        biasp, bspec = _keys_down_bias(
            bias, sq, sk, sq_p, sk_p, seg, block_q,
            lambda h, iq, ks: (h, ks, iq), rows_a_block)
        in_specs.append(bspec)
        args.append(biasp)

    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, sm_scale=sm_scale,
            scale_q=math.frexp(sm_scale)[0] == 0.5, causal=causal,
            window=window, block_q=block_q, block_k=block_k, kv_len=sk,
            chunks=chunks, nseg=nseg, bias_kind=_bias_kind(bias),
            heads=heads, at=site.at, rows_a_block=rows_a_block),
        name="flash_fwd" + _window_suffix(window),
        grid=(b // rows_a_block, h, nq, nseg),
        in_specs=in_specs,
        out_specs=[qspec(dv),
                   site.stat_spec(block_q,
                                  lambda b, h, iq, ks: (b, h, iq))],
        out_shape=[jax.ShapeDtypeStruct(site.shape(h, sq_p, dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h * heads, nq, 1, block_q),
                                        jnp.float32)],
        scratch_shapes=[_scratch((heads, block_q), jnp.float32),
                        _scratch((heads, block_q), jnp.float32),
                        _scratch((heads * dv, block_q), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel",) * 3 + ("arbitrary",),
            vmem_limit_bytes=max(32 << 20, 5 * vmem // 4)),
        interpret=interpret,
    )(*args)
    return (site.unview(o, sq, dv),
            lse.reshape(b, h * heads, sq_p)[:, :, :sq])


_fwd_call_once = jax.jit(_fwd_call, static_argnums=tuple(range(4, 10)),
                         inline=True)


def _scratch(shape, dtype):
    return pltpu.VMEM(shape, dtype)


def _compiler_params(dimension_semantics, **more):
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                **more)


# ---------------------------------------------------------------------------
# backward kernel
# ---------------------------------------------------------------------------

def _bwd_vmem_bytes(chunks, block_q, block_k, d, dv, itemsize, bias_lanes,
                    emit_dbias):
    """Bytes a backward call keeps in VMEM with `chunks` k-blocks of one
    head resident: K, V and the dK, dV blocks double-buffered by the
    pipeline, their f32 accumulators, the bias (and dbias) rows beside
    them; one q-block's operands and dQ; the f32 score-sized values of
    a tile in flight."""
    per_key = (4 * itemsize + 4) * (d + dv) + 8 * bias_lanes
    if emit_dbias:
        per_key += 8 * block_q
    per_step = block_q * (2 * itemsize * (d + dv) + 12 * d + 256)
    return (chunks * block_k * per_key + per_step
            + 6 * 4 * block_q * block_k)


def _bwd_kernel(*refs, sm_scale, causal, window, block_q, block_k, kv_len,
                chunks, nq, group, bias_kind, emit_dbias, heads, at,
                rows_a_block):
    """One q-block against the `chunks` k-blocks of one resident
    k-segment, scores held keys-down ([block_k, block_q]: the row
    statistics are rows, and only dQ's product contracts over a
    transposed operand). Each tile's s, p, dp, ds are computed once and
    feed dV, dK and dQ. The grid's last axis walks the `nq` q-blocks of
    each query head that reads this key head, one head after another:
    dK and dV are summed over the group where they are accumulated.

    On a sequence-major block (`at` == (0,), `heads` heads in its lanes)
    the recurrence runs once a head on q and do with the other heads'
    lanes zeroed: the scores and dp contract over the block's whole
    lanes, dV and dK gain this head's lanes and zeros beside them, and
    of dQ, which k's other lanes fill too, this head's lanes are kept.
    No product gains from a lane slice here (v5e, ms a site of 64 heads
    x 2048 x 64, forward and backward: 2.85 all zeroed, 2.85-2.87 with
    any one of the five sliced, 2.86 with all; causal 2.06, 2.07-2.10,
    2.11): k, v and the accumulators' rows stay whole 128-lane words.
    So the head is the index of a fori_loop, its body traced and
    compiled once (the same ms a site as a static loop; a backward
    kernel compiles in 1.2 s for 1.5, 5 s a step of 18 sites).

    Under the short-sequence plan (_rows_a_block) a block holds
    `rows_a_block` > 1 batch rows (_each_row), and a row's heads (a
    static loop here) hand back their one tile's dV, dK and dQ as
    values: summed, laid side by side and written once, no scratch, no
    `pl.when` (as in _fwd_kernel)."""
    n_in = 6 + (bias_kind is not None)
    q_ref, k_ref, v_ref = refs[:3]
    bias_ref = refs[3] if bias_kind is not None else None
    do_ref, lse_ref, delta_ref = refs[n_in - 3:n_in]
    dq_ref, dk_ref, dv_ref = refs[n_in:n_in + 3]
    dbias_ref = refs[n_in + 3] if emit_dbias else None
    dq_scr, dk_scr, dv_scr = refs[-3:]
    ks, step = pl.program_id(2), pl.program_id(3)
    iq = step if group == 1 else step % nq
    one_tile = rows_a_block > 1
    if one_tile:
        iq = ks = 0

    def _gain(scr, where, product):
        """scr[where] += product(); one tile keeps nothing in scratch
        and is handed the product."""
        if one_tile:
            return product()
        scr[where] += product()

    def _row(at):
        brow = at[0] if bias_kind and bias_ref.shape[0] > 1 else 0

        if not one_tile:
            @pl.when(step == 0)
            def _init():
                dk_scr[:] = jnp.zeros_like(dk_scr)
                dv_scr[:] = jnp.zeros_like(dv_scr)

        def _head(i):
            """Head i's walk: dK and dV gain in scratch, dQ is left in
            dq_scr (one tile: all three are handed back)."""
            if not one_tile:
                dq_scr[:] = jnp.zeros_like(dq_scr)
            if emit_dbias:    # tiles that do not run still own a block
                dbias_ref[at[0], 0] = jnp.zeros_like(dbias_ref[at[0], 0])

            q = _head_lanes(q_ref[at], i, heads)             # [bq, d]
            do = _head_lanes(do_ref[at], i, heads)           # [bq, dv]
            lse = lse_ref[at[0], i, 0]                       # [1, bq]
            delta = delta_ref[at[0], i, 0]
            first = ks * chunks
            # k-blocks past the last key, or wholly above the causal
            # diagonal: nothing to do
            stop = jnp.minimum(chunks, -(-kv_len // block_k) - first)
            if causal:
                stop = jnp.minimum(
                    stop,
                    (iq * block_q + block_q - 1) // block_k + 1 - first)

            def _tile(c, carry, diagonal=causal, lower=False):
                """What tile c adds to its keys' dV and dK and to this
                head's dQ: gained in scratch, or (one tile) handed
                back."""
                rows = pl.ds(pl.multiple_of(c * block_k, block_k),
                             block_k)
                k, v = k_ref[(*at, rows, slice(None))], \
                    v_ref[(*at, rows, slice(None))]
                s = jax.lax.dot_general(
                    k, q, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                if bias_kind == "key":      # one value a key, every lane
                    s = s + bias_ref[brow, 0, rows, :][:, :1].astype(
                        jnp.float32)
                elif bias_kind == "score":
                    s = s + bias_ref[brow, 0, rows, :].astype(jnp.float32)
                kpos = (first + c) * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0)
                if kv_len % block_k:        # mask seq padding
                    s = jnp.where(kpos < kv_len, s, NEG_INF)
                if diagonal:
                    qpos = iq * block_q + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 1)
                    s = _visible(s, qpos, kpos, window if lower else None)
                p = jnp.exp(s - lse)                         # [bk, bq]
                dv = _gain(                                  # [bk, dv]
                    dv_scr, (rows, slice(None)),
                    lambda: jax.lax.dot_general(
                        p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
                dp = jax.lax.dot_general(
                    v, do, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)      # [bk, bq]
                ds = p * (dp - delta)
                if emit_dbias:
                    dbias_ref[at[0], 0, rows, :] = ds
                ds = ds.astype(q.dtype)
                dk = _gain(                                  # [bk, d]
                    dk_scr, (rows, slice(None)),
                    lambda: sm_scale * jax.lax.dot_general(
                        ds, q, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
                dq = _gain(                                  # [bq, d]
                    dq_scr, slice(None),
                    lambda: sm_scale * jax.lax.dot_general(
                        ds, k, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
                return (dv, dk, dq) if one_tile else carry

            if one_tile:
                return _tile(0, None, causal, window is not None)

            if window is None:
                jax.lax.fori_loop(0, stop, _tile, None)
            else:
                _walk_band(_tile, None, iq, first, stop, block_q, block_k,
                           window)

        if one_tile:
            # the whole sequence: the heads' dV and dK summed and their
            # dQ laid side by side as values, nothing waits in scratch
            dv, dk, dq = _head(0)
            for i in range(1, heads):
                dv_i, dk_i, dq_i = _head(i)
                dv, dk = dv + dv_i, dk + dk_i
                dq = _head_lanes(dq_i, i, heads, dq)
            dq_ref[(0, *at)] = dq.astype(dq_ref.dtype)
            dk_ref[at] = dk.astype(dk_ref.dtype)
            dv_ref[at] = dv.astype(dv_ref.dtype)
            return
        if heads == 1:
            _head(0)
            dq_ref[(0, *at)] = dq_scr[:].astype(dq_ref.dtype)
        else:
            def _each(i, _):
                _head(i)
                # of a head's dQ its own lanes; the others' are the
                # other heads' to write
                dq_ref[(0, *at)] = _head_lanes(
                    dq_scr[:].astype(dq_ref.dtype), i, heads,
                    dq_ref[(0, *at)])
            jax.lax.fori_loop(0, heads, _each, None)

        @pl.when(step == pl.num_programs(3) - 1)
        def _fin():
            dk_ref[at] = dk_scr[:].astype(dk_ref.dtype)
            dv_ref[at] = dv_scr[:].astype(dv_ref.dtype)

    _each_row(_row, at, rows_a_block)


_SITE_HELP = {
    "fwd": "flash-attention forward calls traced, by what the call's byte "
           "count against the VMEM budget let it keep resident (resident: "
           "a head's whole K and V, one grid step a q-block; partial: K "
           "and V a segment at a time, the softmax statistics and the "
           "accumulator carried across a q-block's segments in scratch)",
    "bwd": "flash-attention backward calls traced, by what the call's byte "
           "count against the VMEM budget let it keep resident (resident: "
           "a head's whole K and V, so dK, dV and dQ leave the kernel "
           "finished; partial: K and V a segment at a time, dQ written "
           "once a segment in f32 and summed by XLA)",
}


def _count_site(which, plan, window, site):
    from ...observability.registry import default_registry
    path = "relaid" if site.relaid else (
        "resident" if plan.nseg == 1 else "partial")
    default_registry().counter(
        f"paddle_tpu_flash_{which}_sites_total",
        _SITE_HELP[which] + ", by the window (0: none; the kernel is "
        "then named without _window), by the query heads that read one "
        "key head and by the heads a block holds (1: a head-major call, "
        "or a sequence-major one whose heads fill whole 128-lane words; "
        "128 // D on a sequence-major call with narrower heads) and the "
        "batch rows a grid step holds (1 wherever a head's sequence is "
        "more than one tile; the short-sequence plan's choice where it "
        "is one). path relaid: a sequence-major call whose shape the "
        "sequence-major blocks cannot serve, transposed by the entry and "
        "run head-major.",
        ("path", "window", "group", "heads_a_block",
         "rows_a_block")).labels(
            path=path, window=str(window or 0), group=str(site.group),
            heads_a_block=str(site.heads),
            rows_a_block=str(plan.rows_a_block)).inc()


def _window_suffix(window):
    """The readers that match flash_fwd / flash_bwd_dq|dkv count a
    windowed call too; the suffix lets a reader tell it apart."""
    return "" if window is None else "_window"


def _bwd_plan(site, bias, bias_needs_grad, causal, block_q, block_k,
              interpret, itemsize):
    """A backward call's tiles and segments (as _fwd_plan)."""
    block_q, block_k = _blocks(
        site.sq, site.sk,
        *_caps(bias, bias_needs_grad, causal, block_q, block_k), interpret)
    nq, nk = -(-site.sq // block_q), -(-site.sk // block_k)

    def vmem_bytes(chunks):
        return _bwd_vmem_bytes(chunks, block_q, block_k,
                               site.heads * site.d, site.heads * site.dv,
                               itemsize, _bias_lanes(bias, block_q),
                               bias is not None and bias_needs_grad)

    nseg, chunks = _segments(nk, vmem_bytes)
    rows = _rows_a_block(site, nq, nk, vmem_bytes(chunks))
    return _Plan(block_q, block_k, nq, nseg, chunks, rows,
                 rows * vmem_bytes(chunks))


def _bwd(res, g, sm_scale, causal, window, block_q, block_k, interpret,
         bias_needs_grad, layout="bhsd"):
    """(dq, dk, dv, dbias). Counts the site, then _bwd_call, traced once
    a signature where _fwd_call is."""
    q, k, v, bias = res[:4]
    site = _Site(layout, q, k, v)
    plan = _bwd_plan(site, bias, bias_needs_grad, causal, block_q, block_k,
                     interpret, q.dtype.itemsize)
    _count_site("bwd", plan, window, site)
    call = _bwd_call_once if site.seq_major else _bwd_call
    return call(res, g, sm_scale, causal, window, interpret,
                bias_needs_grad, layout, plan)


def _bwd_call(res, g, sm_scale, causal, window, interpret, bias_needs_grad,
              layout, plan):
    q, k, v, bias, o, lse = res
    do = g
    block_q, block_k, nq, nseg, chunks, rows_a_block, vmem = plan
    site = _Site(layout, q, k, v, rows_a_block)
    b, h, hk, sq, sk, d, dv = (site.b, site.h, site.hk, site.sq, site.sk,
                               site.d, site.dv)
    group, heads = site.group, site.heads
    bias_kind = _bias_kind(bias)
    emit_dbias = bias is not None and bias_needs_grad
    seg = chunks * block_k
    sq_p, sk_p = nq * block_q, nseg * seg

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                # [B,H,Sq]
    if site.seq_major:
        delta = jnp.swapaxes(delta, 1, 2)
    qp, dop = site.rows(q, sq_p - sq), site.rows(do, sq_p - sq)
    kp, vp = site.rows(k, sk_p - sk), site.rows(v, sk_p - sk)

    def rows(x):        # a row a q-block; pads read lse 0: exp stays finite
        return jnp.pad(x, ((0, 0), (0, 0), (0, sq_p - sq))).reshape(
            b, h * heads, nq, 1, block_q)

    # the grid is (batch, KEY head, k-segment, step); a step is one
    # q-block of one of the group's query heads
    def at(hk, step):
        if group == 1:
            return hk, step
        return hk * group + step // nq, step % nq

    def qspec(width, lead=None):
        return site.spec(block_q, width,
                         lambda b, hk, ks, st: (b, *at(hk, st)), lead)

    def kspec(width):
        return site.spec(seg, width, lambda b, hk, ks, st: (b, hk, ks))

    rspec = site.stat_spec(block_q, lambda b, hk, ks, st: (b, *at(hk, st)))
    in_specs = [qspec(d), kspec(d), kspec(dv)]
    args = [qp, kp, vp]
    if bias is not None:
        biasp, bspec = _keys_down_bias(
            bias, sq, sk, sq_p, sk_p, seg, block_q,
            lambda hk, ks, st: (at(hk, st)[0], ks, at(hk, st)[1]),
            rows_a_block)
        in_specs.append(bspec)
        args.append(biasp)
    in_specs += [qspec(dv), rspec, rspec]
    args += [dop, rows(lse), rows(delta)]

    # dQ a k-segment: finished where there is one, else an f32 partial
    out_shape = [
        jax.ShapeDtypeStruct((nseg, *site.shape(h, sq_p, d)),
                             q.dtype if nseg == 1 else jnp.float32),
        jax.ShapeDtypeStruct(site.shape(hk, sk_p, d), k.dtype),
        jax.ShapeDtypeStruct(site.shape(hk, sk_p, dv), v.dtype)]
    out_specs = [qspec(d, lead=lambda b, hk, ks, st: ks),
                 kspec(d), kspec(dv)]
    if emit_dbias:
        out_shape.append(jax.ShapeDtypeStruct(
            (b, h, sk_p, sq_p), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (rows_a_block, 1, seg, block_q),
            lambda b, hk, ks, st: (b, at(hk, st)[0], ks, at(hk, st)[1])))

    outs = pl.pallas_call(
        functools.partial(
            _bwd_kernel, sm_scale=sm_scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, kv_len=sk, chunks=chunks,
            nq=nq, group=group, bias_kind=bias_kind,
            emit_dbias=emit_dbias, heads=heads, at=site.at,
            rows_a_block=rows_a_block),
        # the benchmark's readers find the backward by flash_bwd_(dq|dkv)
        name="flash_bwd_dkv_dq" + _window_suffix(window),
        grid=(b // rows_a_block, hk, nseg, group * nq),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[_scratch((block_q, heads * d), jnp.float32),
                        _scratch((seg, heads * d), jnp.float32),
                        _scratch((seg, heads * dv), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel",) * 3 + ("arbitrary",),
            vmem_limit_bytes=max(32 << 20, 5 * vmem // 4)),
        interpret=interpret,
    )(*args)
    dq, dk, dv_ = outs[:3]
    dq = dq[0] if nseg == 1 else jnp.sum(dq, axis=0).astype(q.dtype)
    if emit_dbias:
        dbias = jnp.swapaxes(outs[3], 2, 3)[:, :, :sq, :sk]
        # reduce over every broadcast dim of the original bias
        for ax in range(4):
            if bias.shape[ax] == 1 and dbias.shape[ax] != 1:
                dbias = jnp.sum(dbias, axis=ax, keepdims=True)
        dbias = dbias.astype(bias.dtype)
    else:
        dbias = jnp.zeros_like(bias) if bias is not None else None
    return (site.unview(dq, sq, d), site.unview(dk, sk, d),
            site.unview(dv_, sk, dv), dbias)


_bwd_call_once = jax.jit(_bwd_call, static_argnums=tuple(range(2, 9)),
                         inline=True)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, bias, sm_scale, causal, window, block_q, block_k,
           interpret, bias_grad, layout):
    o, _ = _fwd(q, k, v, bias, sm_scale, causal, window, block_q, block_k,
                interpret, bias_grad, layout)
    return o


def _flash_fwd(q, k, v, bias, sm_scale, causal, window, block_q, block_k,
               interpret, bias_grad, layout):
    o, lse = _fwd(q, k, v, bias, sm_scale, causal, window, block_q,
                  block_k, interpret, bias_grad, layout)
    return o, (q, k, v, bias, o, lse)


def _flash_bwd(sm_scale, causal, window, block_q, block_k, interpret,
               bias_grad, layout, res, g):
    dq, dk, dv, dbias = _bwd(res, g, sm_scale, causal, window, block_q,
                             block_k, interpret, bias_needs_grad=bias_grad,
                             layout=layout)
    return dq, dk, dv, dbias


_flash.defvjp(_flash_fwd, _flash_bwd)


def _seq_major_serves(q, k, v, bias, bias_grad):
    """Whether the sequence-major blocks can hold this call: heads that
    cut the lanes into whole 128-lane words (_heads_a_block), whole
    blocks of them, a block of query heads reading the block of key
    heads at the same lanes (grouped key heads only where a head is a
    block), and no bias but an untrained key row shared by the heads."""
    heads = _heads_a_block(q.shape[3], v.shape[3])
    if heads is None or q.shape[2] % heads or k.shape[2] % heads \
            or (heads > 1 and q.shape[2] != k.shape[2]):
        return False
    return bias is None or (_bias_kind(bias) == "key"
                            and bias.shape[1] == 1 and not bias_grad)


def flash_attention(q, k, v, bias: Optional[jax.Array] = None,
                    causal: bool = False, window: Optional[int] = None,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    bias_grad: bool = False,
                    layout: str = "bhsd") -> jax.Array:
    """Tiled online-softmax attention.

    layout "bhsd" (head-major): q [B, H, Sq, D]; k [B, Hk, Sk, D];
    v [B, Hk, Sk, Dv]; returns [B, H, Sq, Dv]. layout "bshd"
    (sequence-major, what a projection writes and reads): q [B, Sq, H, D];
    k [B, Sk, Hk, D]; v [B, Sk, Hk, Dv]; returns [B, Sq, H, Dv] — the
    kernels read each as its free view [B, S, H * D] through their
    BlockSpecs, a block the fewest heads that fill whole 128-lane words
    (128 // D of them where D = Dv divides 128, one where both are
    multiples of 128), and no array is transposed. A sequence-major call
    the blocks cannot serve (_seq_major_serves: 192-wide keys beside
    128-wide values, a head count that splits a block, grouped key heads
    under heads narrower than a word, a score-sized, per-head or trained
    bias) is transposed here and run head-major; the site counters then
    read path="relaid".

    Hk divides H (query head h reads key head h // (H / Hk)); bias
    additive [B, H, Sq, Sk] with any of the four dims broadcast (size 1),
    in either layout. sm_scale defaults to 1/sqrt(D), Q's width,
    whatever Dv is.

    window=W (causal sites only): query i sees keys i - W < j <= i, its
    own among them (the Hugging Face sliding_window convention). Both
    kernels then walk the band's k-blocks alone and are named
    flash_*_window; a window that reaches every key is plain causal.

    bias_grad=False (default) treats bias as a constant mask: backward
    returns zeros for it without materializing the O(Sq*Sk) dbias buffer.
    Set bias_grad=True for trainable biases (e.g. relative-position bias);
    the gradient is then emitted from the backward kernel a score tile at
    a time and summed over any broadcast dims.

    block_q/block_k act as CAPS on the tile size: the sequence is split
    into the fewest cap-respecting tiles and the tile shrinks to fit
    (minimizing padding) in whole 128-lane columns, so an explicit 256
    with sq=900 runs 4 tiles of 256. None lets _caps choose from what
    the call can see (the bias's kind, causal), one rule for both
    passes, measured on a v5e.
    """
    if layout not in ("bhsd", "bshd"):
        raise ValueError(f"flash_attention: layout {layout!r} is neither "
                         "'bhsd' nor 'bshd'")
    if interpret is None:
        interpret = _interpret_default()
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    head_dim, seq_dim = (2, 1) if layout == "bshd" else (1, 2)
    if q.shape[head_dim] % k.shape[head_dim] \
            or k.shape[head_dim] != v.shape[head_dim]:
        raise ValueError(
            f"flash_attention: {q.shape[head_dim]} query heads over "
            f"{k.shape[head_dim]} key and {v.shape[head_dim]} value heads")
    if window is not None:
        if not causal or int(window) < 1:
            raise ValueError("flash_attention: a window is a whole number "
                             "of keys on a causal site")
        window = None if int(window) >= k.shape[seq_dim] else int(window)
    if bias is not None:
        if bias.ndim == 2:        # [Sq|1, Sk|1]
            bias = bias[None, None]
        elif bias.ndim == 3:      # [B|1, Sq|1, Sk|1]
            bias = bias[:, None]
    relaid = layout == "bshd" and not _seq_major_serves(q, k, v, bias,
                                                        bias_grad)
    if relaid:
        q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
        layout = "relaid"
    o = _flash(q, k, v, bias, float(sm_scale), bool(causal), window,
               None if block_q is None else int(block_q),
               None if block_k is None else int(block_k),
               bool(interpret), bool(bias_grad), layout)
    return jnp.swapaxes(o, 1, 2) if relaid else o
