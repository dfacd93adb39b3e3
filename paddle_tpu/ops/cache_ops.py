"""KV-cache update ops for incremental decode (serving/generation).

The cache is persistable scope state shaped [slots, heads, max_seq, d]:
an op here reads the cache var and writes its output back to the SAME
var name, which makes the executor classify it read-write state and
donate it to the jitted step (core/executor.py donate_argnums) — the
update lands in the donated buffer, not in a copy of the whole cache
per token. This is exactly the optimizer-op ParamOut contract; the
serving engine never fetches the cache, so donation is safe even under
sync dispatch.

What writes: ``kv_cache_write`` (prefill) is one XLA
dynamic-update-slice a cache. ``kv_cache_append`` (decode) on a TPU is
one Pallas kernel a cache (ops/pallas/kv_cache_append.py), its cache
operand aliased to its output: as XLA's batched scatter it ran as a
``while`` over the slots, one dependent tiny copy an iteration, which
was half of a 128-slot decode step. Off the TPU, under a mesh, or for a
cache the kernel cannot serve, it is that batched scatter, and
``paddle_tpu_kv_append_sites_total{path}`` says which was traced.

Both rules are pure differentiable JAX, but generation never runs a
backward pass — the index slots are marked no-grad so an accidental
minimize() over a decode graph fails on the float paths only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op


def _cache_passthrough_infer(block_desc, op):
    """Out mirrors the Cache operand: both ops are in-place
    dynamic-update-slices, so shape/dtype pass straight through. The
    generic abstract trace cannot run them (integer index operands have
    no declared feed values at build time); without this rule the
    memory planner would see a shape-coverage gap exactly on the
    cache-resident buffers it most needs to count."""
    names = op.input("Cache")
    outs = op.output("Out")
    if not names or not outs:
        return {}
    v = block_desc.find_var_recursive(names[0])
    if v is None or v.shape is None:
        return {}
    return {outs[0]: {"shape": list(v.shape), "dtype": v.dtype,
                      "lod_level": 0}}


@register_op("kv_cache_write", no_grad_slots=["Slot"],
             infer_shape=_cache_passthrough_infer)
def _kv_cache_write(ctx):
    """Prefill path: write one request's full-prompt K or V rows into
    its cache slot.

    Cache: [slots, h, max_seq, d]; New: [1, h, S, d] (S <= max_seq);
    Slot: [1] int — the in-flight batch slot index. Rows [0, S) of the
    slot are overwritten; rows beyond S keep whatever the previous
    occupant left (beyond the length the decode step's attention is
    handed, so masked or never read).
    """
    cache = ctx.input("Cache")
    new = ctx.input("New").astype(cache.dtype)
    slot = ctx.input("Slot").reshape(()).astype(jnp.int32)
    ctx.set_output("Out", jax.lax.dynamic_update_slice(
        cache, new, (slot, 0, 0, 0)))


def device_lane_axis(shape, dtype, device=None):
    """The axis of a ``shape`` / ``dtype`` array that ``device`` (the
    first attached one by default) holds minormost, on a TPU its 128
    lanes: the backend's default layout, which is a function of shape
    and dtype alone. A [slots, h, max_seq, 64] cache answers 2 on a
    v5e (positions on the lanes), a d_key of 128 answers 3."""
    from jax.experimental.layout import Layout
    device = jax.devices()[0] if device is None else device
    layout = Layout.from_pjrt_layout(device.client.get_default_layout(
        np.dtype(dtype), tuple(shape), device))
    return layout.major_to_minor[-1]


def _append_kernel_lane_axis(ctx, cache):
    """Which path a kv_cache_append site takes, decided on what the
    trace can observe: the lane axis to hand the Pallas kernel, or None
    for the batched scatter. The kernel runs on a TPU backend, outside
    a mesh (GSPMD cannot partition a Mosaic call) and for a cache it
    can serve as the device holds it; there is no knob."""
    from .pallas.kv_cache_append import fits
    if jax.default_backend() != "tpu" or ctx.extra.get("mesh") is not None:
        return None
    lane_axis = device_lane_axis(cache.shape, cache.dtype)
    return lane_axis if fits(cache.shape, cache.dtype, lane_axis) else None


def _count_append_site(ctx, path):
    """One count a site traced into a step program: the executor's
    trace carries its program in ``extra``, the build-time shape
    inference (framework.infer_op_outputs) runs the same rule without
    one and is not a site of any executable."""
    if "program" not in ctx.extra:
        return
    from ..observability.registry import default_registry
    default_registry().counter(
        "paddle_tpu_kv_append_sites_total",
        "kv_cache_append sites traced into a step program, by the path "
        "that writes the new rows: kernel (one Pallas TPU call a cache) "
        "or scatter (XLA's batched scatter, a loop over the slots on a "
        "TPU).",
        ("path",)).labels(path=path).inc()


@register_op("kv_cache_append", no_grad_slots=["Pos"],
             infer_shape=_cache_passthrough_infer)
def _kv_cache_append(ctx):
    """Decode path: append one token's K or V row per slot, at each
    slot's own position.

    Cache: [slots, h, max_seq, d]; New: [slots, h, 1, d]; Pos: [slots]
    int — per-slot write position. Inactive slots point Pos at 0; the
    garbage row is overwritten by that slot's next prefill and is never
    attended to meanwhile (an idle slot's live length is 0).

    On a TPU all slots' rows reach the cache in one Pallas call that
    touches one tile a slot (_append_kernel_lane_axis says when);
    elsewhere in one batched scatter. Both write the same bits.
    """
    cache = ctx.input("Cache")
    new = ctx.input("New").astype(cache.dtype)
    pos = ctx.input("Pos").astype(jnp.int32)
    lane_axis = _append_kernel_lane_axis(ctx, cache)
    if lane_axis is None:
        _count_append_site(ctx, "scatter")
        out = jax.vmap(
            lambda c, n, p: jax.lax.dynamic_update_slice(c, n, (0, p, 0)))(
                cache, new, pos)
    else:
        from .pallas.kv_cache_append import kv_cache_append
        _count_append_site(ctx, "kernel")
        out = kv_cache_append(cache, new, pos, lane_axis=lane_axis)
    ctx.set_output("Out", out)
