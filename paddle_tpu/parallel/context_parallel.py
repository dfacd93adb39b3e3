"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference predates sequence parallelism (SURVEY.md §5: its long-sequence
story is LoD ragged batching + DynamicRNN); this module is the TPU-native
long-context capability the rebuild treats as first-class. Two schemes:

- **Ring attention** (`ring_attention`): q stays put; K/V blocks rotate
  around the 'seq' mesh axis via `jax.lax.ppermute` over ICI, with online
  (flash-style) softmax accumulation. A custom VJP re-rotates K/V together
  with their gradient accumulators in the backward pass, so per-device
  memory stays O(S_local) — no O(S^2) scores and no all-gathered KV, in
  either pass.

- **Ulysses all-to-all** (`ulysses_attention`): `jax.lax.all_to_all`
  reshards [B, H, S/n, D] -> [B, H/n, S, D], runs ordinary (or Pallas
  flash) attention on full sequences with a head shard, and reshards back.
  Requires num_heads % axis_size == 0.

Both run *inside* `jax.shard_map`; `sequence_parallel_attention` is the
outer wrapper that takes globally-sharded arrays. All math accumulates in
float32 regardless of input dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _scale(q, sm_scale):
    return 1.0 / (q.shape[-1] ** 0.5) if sm_scale is None else sm_scale


def _chunk_scores(q, k, sm_scale, causal, q_start, k_start):
    """Scores [B,H,Sq,Sk] for a (q chunk, k chunk) pair with global
    positions q_start+i / k_start+j for the causal mask."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        qpos = q_start + jnp.arange(q.shape[2])[:, None]
        kpos = k_start + jnp.arange(k.shape[2])[None, :]
        s = jnp.where((qpos >= kpos)[None, None], s, NEG_INF)
    return s


def _ring_perm(n):
    # each device hands its current KV block to the next ring neighbour
    return [(j, (j + 1) % n) for j in range(n)]


def _ring_fwd_scan(q, k, v, kv_mask, axis_name, causal, sm_scale):
    """Forward ring pass. Returns (o, lse); lse is [B,H,S,1] float32.
    kv_mask: optional additive row mask [B, Sk_local] that rotates with
    its K/V block (covers padding masks; full [Sq,Sk] biases are not
    ring-compatible — use the causal flag for causality)."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    k_loc = k.shape[2]
    sm = _scale(q, sm_scale)
    q_start = idx * s_loc

    def step(carry, _):
        k_cur, v_cur, mask_cur, t, m, l, acc = carry
        # after t rotations this device holds the block that started on
        # ring neighbour (idx - t) mod n
        k_start = ((idx - t) % n) * k_loc
        s = _chunk_scores(q, k_cur, sm, causal, q_start, k_start)
        if mask_cur is not None:
            s = s + mask_cur[:, None, None, :].astype(jnp.float32)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32))
        perm = _ring_perm(n)
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        if mask_cur is not None:
            mask_cur = jax.lax.ppermute(mask_cur, axis_name, perm)
        return (k_cur, v_cur, mask_cur, t + 1, m_new, l, acc), None

    m0 = jnp.full((b, h, s_loc, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    (k_fin, v_fin, _, _, m, l, acc), _ = jax.lax.scan(
        step, (k, v, kv_mask, jnp.int32(0), m0, l0, acc0), None, length=n)
    del k_fin, v_fin  # blocks are back home after a full cycle
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = (acc / l_safe).astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-37))
    return o, lse


def _ring_bwd_scan(q, k, v, kv_mask, o, lse, do, axis_name, causal,
                   sm_scale):
    """Backward ring pass: K/V blocks rotate together with their dk/dv
    accumulators, so each block arrives home with every device's
    contribution after a full cycle. Per-device memory stays O(S_local)."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    k_loc = k.shape[2]
    sm = _scale(q, sm_scale)
    q_start = idx * s_loc
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)          # [B,H,S,1]
    do32 = do.astype(jnp.float32)

    def step(carry, _):
        k_cur, v_cur, mask_cur, dk_cur, dv_cur, t, dq = carry
        k_start = ((idx - t) % n) * k_loc
        s = _chunk_scores(q, k_cur, sm, causal, q_start, k_start)
        if mask_cur is not None:
            s = s + mask_cur[:, None, None, :].astype(jnp.float32)
        p = jnp.exp(s - lse)                          # [B,H,Sq,Sk]
        dv_cur = dv_cur + jnp.einsum("bhqk,bhqd->bhkd", p, do32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do32,
                        v_cur.astype(jnp.float32))
        ds = p * (dp - delta)                         # [B,H,Sq,Sk]
        dq = dq + sm * jnp.einsum("bhqk,bhkd->bhqd", ds,
                                  k_cur.astype(jnp.float32))
        dk_cur = dk_cur + sm * jnp.einsum("bhqk,bhqd->bhkd", ds,
                                          q.astype(jnp.float32))
        perm = _ring_perm(n)
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        if mask_cur is not None:
            mask_cur = jax.lax.ppermute(mask_cur, axis_name, perm)
        dk_cur = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = jax.lax.ppermute(dv_cur, axis_name, perm)
        return (k_cur, v_cur, mask_cur, dk_cur, dv_cur, t + 1, dq), None

    zeros_kd = jnp.zeros((b, h, k_loc, d), jnp.float32)
    zeros_qd = jnp.zeros((b, h, s_loc, d), jnp.float32)
    (k_fin, v_fin, _, dk, dv, _, dq), _ = jax.lax.scan(
        step, (k, v, kv_mask, zeros_kd, zeros_kd, jnp.int32(0), zeros_qd),
        None, length=n)
    del k_fin, v_fin
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def ring_attention(q, k, v, kv_mask=None, axis_name: str = "seq",
                   causal: bool = False,
                   sm_scale: Optional[float] = None):
    """Ring attention over a mesh axis (call inside shard_map).

    q/k/v: the *local* sequence shard [B, H, S_local, D]; sequence is
    sharded over `axis_name`. Causal masking uses global positions
    (device i holds positions [i*S_local, (i+1)*S_local)). kv_mask is an
    optional additive key-row mask [B, Sk_local] (padding masks); it is a
    constant — no gradient flows to it."""
    o, _ = _ring_fwd_scan(q, k, v, kv_mask, axis_name, causal, sm_scale)
    return o


def _ring_vjp_fwd(q, k, v, kv_mask, axis_name, causal, sm_scale):
    o, lse = _ring_fwd_scan(q, k, v, kv_mask, axis_name, causal, sm_scale)
    return o, (q, k, v, kv_mask, o, lse)


def _ring_vjp_bwd(axis_name, causal, sm_scale, res, do):
    q, k, v, kv_mask, o, lse = res
    dq, dk, dv = _ring_bwd_scan(q, k, v, kv_mask, o, lse, do, axis_name,
                                causal, sm_scale)
    dmask = None if kv_mask is None else jnp.zeros_like(kv_mask)
    return dq, dk, dv, dmask


ring_attention.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ulysses_attention(q, k, v, kv_mask=None, axis_name: str = "seq",
                      causal: bool = False,
                      sm_scale: Optional[float] = None,
                      use_flash: Optional[bool] = None):
    """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism: reshard
    seq-sharded -> head-sharded, attend over the full sequence locally,
    reshard back. Call inside shard_map; requires H % axis_size == 0.
    kv_mask [B, Sk_local] is all-gathered to full length (it is tiny)."""
    n = jax.lax.psum(1, axis_name)
    b, h, s_loc, d = q.shape
    if h % n != 0:
        raise ValueError(
            f"ulysses_attention needs num_heads ({h}) divisible by the "
            f"'{axis_name}' axis size ({n}); use impl='ring' for more "
            "devices than heads")
    # [B, H, S/n, D] -> [B, H/n, S, D]
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                            split_axis=1, concat_axis=2, tiled=True)
    qf, kf, vf = a2a(q), a2a(k), a2a(v)
    bias = None
    if kv_mask is not None:
        full = jax.lax.all_gather(kv_mask, axis_name, axis=1, tiled=True)
        bias = full[:, None, None, :]                  # [B,1,1,Sk]
    if use_flash is None:
        # one routing policy with ops/nn_ops._sdpa: the flash module's
        # crossover, held against the GATHERED sequence
        from ..ops.pallas.flash_attention import FLASH_CROSSOVER_SEQ
        use_flash = (jax.default_backend() == "tpu"
                     and qf.shape[2] >= FLASH_CROSSOVER_SEQ)
    if use_flash:
        from ..ops.pallas import flash_attention
        of = flash_attention(qf, kf, vf, bias, causal=causal,
                             sm_scale=sm_scale)
    else:
        sm = _scale(q, sm_scale)
        s = _chunk_scores(qf, kf, sm, causal, 0, 0)
        if bias is not None:
            s = s + bias.astype(jnp.float32)
        p = jax.nn.softmax(s, axis=-1)
        of = jnp.einsum("bhqk,bhkd->bhqd", p,
                        vf.astype(p.dtype)).astype(q.dtype)
    # [B, H/n, S, D] -> [B, H, S/n, D]
    return jax.lax.all_to_all(of, axis_name=axis_name, split_axis=2,
                              concat_axis=1, tiled=True)


def sequence_parallel_attention(q, k, v, mesh: Mesh, axis: str = "seq",
                                impl: str = "ring", causal: bool = False,
                                sm_scale: Optional[float] = None,
                                kv_mask=None, batch_axis=None,
                                head_axis=None):
    """Outer wrapper: q/k/v are global [B, H, S, D] arrays (or tracers)
    with S sharded over `axis`; runs the chosen scheme via shard_map.
    kv_mask: optional global additive key mask [B, Sk] (padding).

    batch_axis/head_axis name mesh axes the batch/head dims are sharded
    over (DP/TP); carrying them in the specs keeps attention sharded
    across those axes instead of replicating and recomputing it on every
    (data, model) slice. Attention is independent across batch and heads,
    so the ring/all-to-all collectives still only span `axis`.

    This is the TPU-native long-context replacement for what the
    reference could not do at all (no CP in 2018-era PaddlePaddle)."""
    if impl == "ring":
        inner = functools.partial(ring_attention, axis_name=axis,
                                  causal=causal, sm_scale=sm_scale)
    elif impl == "ulysses":
        inner = functools.partial(ulysses_attention, axis_name=axis,
                                  causal=causal, sm_scale=sm_scale)
    else:
        raise ValueError(f"unknown sequence-parallel impl {impl!r}")

    def _usable(name, dim):
        return (name is not None and name in mesh.axis_names
                and dim % mesh.shape[name] == 0)

    b_ax = batch_axis if _usable(batch_axis, q.shape[0]) else None
    h_ax = head_axis if _usable(head_axis, q.shape[1]) else None
    spec = P(b_ax, h_ax, axis, None)
    mspec = P(b_ax, axis)
    if kv_mask is None:
        fn = jax.shard_map(lambda q, k, v: inner(q, k, v),
                           mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
        return fn(q, k, v)
    fn = jax.shard_map(inner, mesh=mesh,
                       in_specs=(spec, spec, spec, mspec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v, kv_mask)
