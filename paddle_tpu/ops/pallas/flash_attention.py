"""Flash attention as Pallas TPU kernels (forward + backward).

Online-softmax tiled attention: O(S) memory instead of the O(S^2) scores
matrix of the naive composition (reference composes attention from
matmul/softmax in python/paddle/fluid/nets.py:312; its hand-fused CUDA
analogue for recurrent hot loops is paddle/cuda/src/hl_cuda_lstm.cu —
Pallas is the TPU-native equivalent of that hand-fusion layer).

Layout: q [B, H, Sq, D], k [B, Hk, Sk, D], v [B, Hk, Sk, Dv] (Dv may
differ from D: latent attention keeps 192-wide keys beside 128-wide
values; the scale stays Q's. Hk divides H: grouped-query attention, query
head h reads key head h // (H / Hk) through the K and V BlockSpecs, and
no H-head copy of K or V exists), out [B, H, Sq, Dv], optional additive
bias/mask broadcastable as [B, {1|H}, Sq, Sk].

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
k-segments, walked by the same kernel.

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


# What one call may plan to keep in VMEM: half of a v5e core's 128 MiB,
# the rest being Mosaic's own. A call asks for what it counted
# (vmem_limit_bytes), not for the default scoped 16 MiB.
_VMEM_BUDGET = 64 << 20


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


def _keys_down_bias(bias, sq, sk, sq_p, sk_p, seg, block_q, where):
    """A bias as the keys-down kernels read it, broadcast dims of batch
    and head kept unmaterialized: a key row as one value a key on every
    lane, [.., sk_p, 128]; a score-sized one turned, [.., sk_p, sq_p].
    `where` picks (query head, k-segment, q-block) out of the grid's
    last three indices. Returns the array and its BlockSpec."""
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

    return biasp, pl.BlockSpec((1, 1, seg, _bias_lanes(bias, block_q)), at)


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


def _fwd_kernel(*refs, sm_scale, scale_q, causal, window, block_q, block_k,
                kv_len, chunks, nseg, bias_kind):
    """One q-block against the `chunks` k-blocks of one resident
    k-segment, scores held keys-down ([block_k, block_q]): the running
    max and sum reduce down sublanes and travel between tiles as
    [1, block_q] rows, the accumulator is [dv, block_q] and is turned
    once a q-block. Across the segments of a head too long to stay
    resident the rows and the accumulator wait in scratch."""
    q_ref, k_ref, v_ref = refs[:3]
    bias_ref = refs[3] if bias_kind is not None else None
    o_ref, lse_ref, m_scr, l_scr, acc_scr = refs[-5:]
    iq, ks = pl.program_id(2), pl.program_id(3)

    @pl.when(ks == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                                          # [bq, d]
    if scale_q:       # a power of two: the scaled scores bit for bit
        q = q * sm_scale
    first = ks * chunks if nseg > 1 else 0
    # k-blocks past the last key, or wholly above the causal diagonal:
    # nothing to do
    stop = -(-kv_len // block_k) - first
    stop = min(chunks, stop) if nseg == 1 else jnp.minimum(chunks, stop)
    if causal:
        stop = jnp.minimum(
            stop, (iq * block_q + block_q - 1) // block_k + 1 - first)

    def _tile(c, carry, diagonal, lower=False):
        m, l = carry                                         # [1, bq]
        rows = pl.ds(pl.multiple_of(c * block_k, block_k), block_k)
        k, v = k_ref[0, 0, rows, :], v_ref[0, 0, rows, :]
        s = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bk, bq]
        if not scale_q:
            s = s * sm_scale
        if bias_kind == "key":      # one value a key, on every lane
            s = s + bias_ref[0, 0, rows, :][:, :1].astype(jnp.float32)
        elif bias_kind == "score":
            s = s + bias_ref[0, 0, rows, :].astype(jnp.float32)
        if kv_len % block_k or diagonal:
            kpos = (first + c) * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
        if kv_len % block_k:        # mask seq padding
            s = jnp.where(kpos < kv_len, s, NEG_INF)
        if diagonal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = _visible(s, qpos, kpos, window if lower else None)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)                               # [bk, bq]
        l = alpha * l + jnp.sum(p, axis=0, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [dv, bq]
        return m_new, l

    carry = m_scr[:], l_scr[:]
    if window is not None:
        m, l = _walk_band(_tile, carry, iq, first, stop, block_q, block_k,
                          window)
    else:
        # the causal select only in the tiles the diagonal crosses: the
        # whole tiles below it first
        whole = 0
        if causal:
            whole = jnp.clip((iq * block_q + 1) // block_k - first, 0,
                             stop)
            carry = jax.lax.fori_loop(
                0, whole, functools.partial(_tile, diagonal=False), carry)
        # a short walk of a known length is unrolled, so that the next
        # tile's products overlap this tile's softmax (v5e, a site of 64
        # heads x 2048 x 64 not causal: 1.10 against 1.16 ms)
        m, l = jax.lax.fori_loop(
            whole, stop, functools.partial(_tile, diagonal=causal), carry,
            unroll=True if isinstance(stop, int) and stop <= 4 else None)
    m_scr[:], l_scr[:] = m, l

    @pl.when(ks == nseg - 1)
    def _fin():
        safe = jnp.where(l == 0.0, 1.0, l)    # fully-masked rows -> 0 out
        o_ref[0, 0] = (acc_scr[:] / safe).T.astype(o_ref.dtype)
        lse_ref[0, 0, 0] = m + jnp.log(jnp.maximum(l, 1e-37))


def _fwd(q, k, v, bias, sm_scale, causal, window, block_q, block_k,
         interpret, bias_grad):
    """o [B, H, Sq, Dv] and the logsumexp [B, H, Sq] f32."""
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    group = h // k.shape[1]
    block_q, block_k = _blocks(
        sq, sk, *_caps(bias, bias_grad, causal, block_q, block_k), interpret)
    nq, nk = -(-sq // block_q), -(-sk // block_k)

    def vmem_bytes(chunks):
        return _fwd_vmem_bytes(chunks, block_q, block_k, d, dv,
                               q.dtype.itemsize,
                               _bias_lanes(bias, block_q))

    nseg, chunks = _segments(nk, vmem_bytes)
    seg = chunks * block_k
    sq_p, sk_p = nq * block_q, nseg * seg
    _count_site("fwd", "resident" if nseg == 1 else "partial", window,
                group)

    pad_k = ((0, 0), (0, 0), (0, sk_p - sk), (0, 0))
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    kp, vp = jnp.pad(k, pad_k), jnp.pad(v, pad_k)

    def qspec(width):
        return pl.BlockSpec((1, 1, block_q, width),
                            lambda b, h, iq, ks: (b, h, iq, 0))

    def kspec(width):        # a group's query heads read one key head
        return pl.BlockSpec(
            (1, 1, seg, width),
            lambda b, h, iq, ks: (b, h if group == 1 else h // group,
                                  ks, 0))

    in_specs = [qspec(d), kspec(d), kspec(dv)]
    args = [qp, kp, vp]
    if bias is not None:
        biasp, bspec = _keys_down_bias(
            bias, sq, sk, sq_p, sk_p, seg, block_q,
            lambda h, iq, ks: (h, ks, iq))
        in_specs.append(bspec)
        args.append(biasp)

    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, sm_scale=sm_scale,
            scale_q=math.frexp(sm_scale)[0] == 0.5, causal=causal,
            window=window, block_q=block_q, block_k=block_k, kv_len=sk,
            chunks=chunks, nseg=nseg, bias_kind=_bias_kind(bias)),
        name="flash_fwd" + _window_suffix(window),
        grid=(b, h, nq, nseg),
        in_specs=in_specs,
        out_specs=[qspec(dv),
                   pl.BlockSpec((1, 1, 1, 1, block_q),
                                lambda b, h, iq, ks: (b, h, iq, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, h, sq_p, dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h, nq, 1, block_q),
                                        jnp.float32)],
        scratch_shapes=[_scratch((1, block_q), jnp.float32),
                        _scratch((1, block_q), jnp.float32),
                        _scratch((dv, block_q), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel",) * 3 + ("arbitrary",),
            vmem_limit_bytes=max(32 << 20, 5 * vmem_bytes(chunks) // 4)),
        interpret=interpret,
    )(*args)
    return o[:, :, :sq], lse.reshape(b, h, sq_p)[:, :, :sq]


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
                chunks, nq, group, bias_kind, emit_dbias):
    """One q-block against the `chunks` k-blocks of one resident
    k-segment, scores held keys-down ([block_k, block_q]: the row
    statistics are rows, and only dQ's product contracts over a
    transposed operand). Each tile's s, p, dp, ds are computed once and
    feed dV, dK and dQ. The grid's last axis walks the `nq` q-blocks of
    each query head that reads this key head, one head after another:
    dK and dV are summed over the group where they are accumulated."""
    n_in = 6 + (bias_kind is not None)
    q_ref, k_ref, v_ref = refs[:3]
    bias_ref = refs[3] if bias_kind is not None else None
    do_ref, lse_ref, delta_ref = refs[n_in - 3:n_in]
    dq_ref, dk_ref, dv_ref = refs[n_in:n_in + 3]
    dbias_ref = refs[n_in + 3] if emit_dbias else None
    dq_scr, dk_scr, dv_scr = refs[-3:]
    ks, step = pl.program_id(2), pl.program_id(3)
    iq = step if group == 1 else step % nq

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    dq_scr[:] = jnp.zeros_like(dq_scr)
    if emit_dbias:        # the tiles that do not run still own a block
        dbias_ref[0, 0] = jnp.zeros_like(dbias_ref[0, 0])

    q, do = q_ref[0, 0], do_ref[0, 0]          # [bq, d], [bq, dv]
    lse, delta = lse_ref[0, 0, 0], delta_ref[0, 0, 0]       # [1, bq]
    first = ks * chunks
    # k-blocks past the last key, or wholly above the causal diagonal:
    # nothing to do
    stop = jnp.minimum(chunks, -(-kv_len // block_k) - first)
    if causal:
        stop = jnp.minimum(
            stop, (iq * block_q + block_q - 1) // block_k + 1 - first)

    def _tile(c, carry, diagonal=causal, lower=False):
        rows = pl.ds(pl.multiple_of(c * block_k, block_k), block_k)
        k, v = k_ref[0, 0, rows, :], v_ref[0, 0, rows, :]
        s = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bk, bq]
        if bias_kind == "key":      # one value a key, on every lane
            s = s + bias_ref[0, 0, rows, :][:, :1].astype(jnp.float32)
        elif bias_kind == "score":
            s = s + bias_ref[0, 0, rows, :].astype(jnp.float32)
        kpos = (first + c) * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        if kv_len % block_k:        # mask seq padding
            s = jnp.where(kpos < kv_len, s, NEG_INF)
        if diagonal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = _visible(s, qpos, kpos, window if lower else None)
        p = jnp.exp(s - lse)                                 # [bk, bq]
        dv_scr[rows, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bk, dv]
        dp = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bk, bq]
        ds = p * (dp - delta)
        if emit_dbias:
            dbias_ref[0, 0, rows, :] = ds
        ds = ds.astype(q.dtype)
        dk_scr[rows, :] += sm_scale * jax.lax.dot_general(
            ds, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bk, d]
        dq_scr[:] += sm_scale * jax.lax.dot_general(
            ds, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bq, d]
        return carry

    if window is None:
        jax.lax.fori_loop(0, stop, _tile, None)
    else:
        _walk_band(_tile, None, iq, first, stop, block_q, block_k, window)
    dq_ref[0, 0, 0] = dq_scr[:].astype(dq_ref.dtype)

    @pl.when(step == pl.num_programs(3) - 1)
    def _fin():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


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


def _count_site(which, path, window, group):
    from ...observability.registry import default_registry
    default_registry().counter(
        f"paddle_tpu_flash_{which}_sites_total",
        _SITE_HELP[which] + ", by the window (0: none; the kernel is "
        "then named without _window) and by the query heads that read "
        "one key head.",
        ("path", "window", "group")).labels(
            path=path, window=str(window or 0), group=str(group)).inc()


def _window_suffix(window):
    """The readers that match flash_fwd / flash_bwd_dq|dkv count a
    windowed call too; the suffix lets a reader tell it apart."""
    return "" if window is None else "_window"


def _bwd(res, g, sm_scale, causal, window, block_q, block_k, interpret,
         bias_needs_grad):
    q, k, v, bias, o, lse = res
    do = g
    b, h, sq, d = q.shape
    hk, sk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = h // hk
    bias_kind = _bias_kind(bias)
    emit_dbias = bias is not None and bias_needs_grad
    block_q, block_k = _blocks(
        sq, sk, *_caps(bias, bias_needs_grad, causal, block_q, block_k),
        interpret)
    nq, nk = -(-sq // block_q), -(-sk // block_k)

    def vmem_bytes(chunks):
        return _bwd_vmem_bytes(chunks, block_q, block_k, d, dv,
                               q.dtype.itemsize,
                               _bias_lanes(bias, block_q), emit_dbias)

    nseg, chunks = _segments(nk, vmem_bytes)
    seg = chunks * block_k
    sq_p, sk_p = nq * block_q, nseg * seg
    _count_site("bwd", "resident" if nseg == 1 else "partial", window,
                group)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                # [B,H,Sq]
    pad_q = ((0, 0), (0, 0), (0, sq_p - sq), (0, 0))
    pad_k = ((0, 0), (0, 0), (0, sk_p - sk), (0, 0))
    qp, dop = jnp.pad(q, pad_q), jnp.pad(do, pad_q)
    kp, vp = jnp.pad(k, pad_k), jnp.pad(v, pad_k)

    def rows(x):        # a row a q-block; pads read lse 0: exp stays finite
        return jnp.pad(x, pad_q[:3]).reshape(b, h, nq, 1, block_q)

    # the grid is (batch, KEY head, k-segment, step); a step is one
    # q-block of one of the group's query heads
    def at(hk, step):
        if group == 1:
            return hk, step
        return hk * group + step // nq, step % nq

    def qspec(width):
        return pl.BlockSpec((1, 1, block_q, width),
                            lambda b, hk, ks, st: (b, *at(hk, st), 0))

    def kspec(width):
        return pl.BlockSpec((1, 1, seg, width),
                            lambda b, hk, ks, st: (b, hk, ks, 0))

    rspec = pl.BlockSpec((1, 1, 1, 1, block_q),
                         lambda b, hk, ks, st: (b, *at(hk, st), 0, 0))
    in_specs = [qspec(d), kspec(d), kspec(dv)]
    args = [qp, kp, vp]
    if bias is not None:
        biasp, bspec = _keys_down_bias(
            bias, sq, sk, sq_p, sk_p, seg, block_q,
            lambda hk, ks, st: (at(hk, st)[0], ks, at(hk, st)[1]))
        in_specs.append(bspec)
        args.append(biasp)
    in_specs += [qspec(dv), rspec, rspec]
    args += [dop, rows(lse), rows(delta)]

    # dQ a k-segment: finished where there is one, else an f32 partial
    out_shape = [
        jax.ShapeDtypeStruct((nseg, b, h, sq_p, d),
                             q.dtype if nseg == 1 else jnp.float32),
        jax.ShapeDtypeStruct((b, hk, sk_p, d), k.dtype),
        jax.ShapeDtypeStruct((b, hk, sk_p, dv), v.dtype)]
    out_specs = [
        pl.BlockSpec((1, 1, 1, block_q, d),
                     lambda b, hk, ks, st: (ks, b, *at(hk, st), 0)),
        kspec(d), kspec(dv)]
    if emit_dbias:
        out_shape.append(jax.ShapeDtypeStruct(
            (b, h, sk_p, sq_p), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (1, 1, seg, block_q),
            lambda b, hk, ks, st: (b, at(hk, st)[0], ks, at(hk, st)[1])))

    outs = pl.pallas_call(
        functools.partial(
            _bwd_kernel, sm_scale=sm_scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, kv_len=sk, chunks=chunks,
            nq=nq, group=group, bias_kind=bias_kind,
            emit_dbias=emit_dbias),
        # the benchmark's readers find the backward by flash_bwd_(dq|dkv)
        name="flash_bwd_dkv_dq" + _window_suffix(window),
        grid=(b, hk, nseg, group * nq),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[_scratch((block_q, d), jnp.float32),
                        _scratch((seg, d), jnp.float32),
                        _scratch((seg, dv), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel",) * 3 + ("arbitrary",),
            vmem_limit_bytes=max(32 << 20, 5 * vmem_bytes(chunks) // 4)),
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
    return dq[:, :, :sq], dk[:, :, :sk], dv_[:, :, :sk], dbias


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, bias, sm_scale, causal, window, block_q, block_k,
           interpret, bias_grad):
    o, _ = _fwd(q, k, v, bias, sm_scale, causal, window, block_q, block_k,
                interpret, bias_grad)
    return o


def _flash_fwd(q, k, v, bias, sm_scale, causal, window, block_q, block_k,
               interpret, bias_grad):
    o, lse = _fwd(q, k, v, bias, sm_scale, causal, window, block_q,
                  block_k, interpret, bias_grad)
    return o, (q, k, v, bias, o, lse)


def _flash_bwd(sm_scale, causal, window, block_q, block_k, interpret,
               bias_grad, res, g):
    dq, dk, dv, dbias = _bwd(res, g, sm_scale, causal, window, block_q,
                             block_k, interpret, bias_needs_grad=bias_grad)
    return dq, dk, dv, dbias


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, bias: Optional[jax.Array] = None,
                    causal: bool = False, window: Optional[int] = None,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    bias_grad: bool = False) -> jax.Array:
    """Tiled online-softmax attention.

    q: [B, H, Sq, D]; k: [B, Hk, Sk, D]; v: [B, Hk, Sk, Dv], Hk a divisor
    of H (query head h reads key head h // (H / Hk)); bias additive with
    any of the four dims broadcast (size 1). Returns [B, H, Sq, Dv].
    sm_scale defaults to 1/sqrt(D), Q's width, whatever Dv is.

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
    if interpret is None:
        interpret = _interpret_default()
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if q.shape[1] % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(f"flash_attention: {q.shape[1]} query heads over "
                         f"{k.shape[1]} key and {v.shape[1]} value heads")
    if window is not None:
        if not causal or int(window) < 1:
            raise ValueError("flash_attention: a window is a whole number "
                             "of keys on a causal site")
        window = None if int(window) >= k.shape[2] else int(window)
    if bias is not None:
        if bias.ndim == 2:        # [Sq|1, Sk|1]
            bias = bias[None, None]
        elif bias.ndim == 3:      # [B|1, Sq|1, Sk|1]
            bias = bias[:, None]
    return _flash(q, k, v, bias, float(sm_scale), bool(causal), window,
                  None if block_q is None else int(block_q),
                  None if block_k is None else int(block_k),
                  bool(interpret), bool(bias_grad))
