"""Sparse optimizer application for row-sharded tables: unique-ids
dedup + scatter row updates with row-wise (lazy) slot state.

Reference capability (SURVEY.md sparse/embedding distribution):
SelectedRows gradients + the pserver-side sparse optimizer
(ParameterServer2 sparse update path, sgd/adagrad/adam SelectedRows
branches) — only the rows a batch touched are read, updated, and
written, so update cost scales with TOUCHED rows, never with vocab.

TPU-native shape: the deduped (ids, row-grads) pair is replicated (the
row gradients come out of the psum-assembled forward, so every shard
already holds them); each shard gathers its OWN slice of the touched
rows, runs the identical dense update formulas
(ops/optimizer_ops.sparse_row_update) on that block, and scatters the
results back locally. No collective crosses the model axis during
apply — the only model-axis traffic of a training step is the forward
gather's psum.

Bit-identity contract: on rows present in the update, the result is
bit-identical to the dense single-chip optimizer ops (same formula
expressions, same dtype, elementwise) — tested 3-step in
tests/test_embedding_subsystem.py. Rows NOT in the update keep their
param AND slot state (lazy semantics; see KNOWN_GAPS on adam).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..ops.optimizer_ops import SPARSE_HYPER_DEFAULTS, sparse_row_update

#: per-kind row slots, in the order the dense op reads them
ROW_SLOTS = {"sgd": (), "adagrad": ("moment",),
             "adam": ("moment1", "moment2")}
#: per-kind scalar slots ([1]-shaped, replicated, advanced per step)
SCALAR_SLOTS = {"sgd": (), "adagrad": (),
                "adam": ("beta1_pow", "beta2_pow")}


def dedup_ids(ids, vocab: int, padding_idx: Optional[int] = None):
    """Unique touched rows of an id batch, at static size.

    Returns ``(uniq, inv, valid)`` with ``uniq.shape == (ids.size,)``:
    ids are clipped to ``[0, vocab)`` first (the dense lookup's clip
    semantics, so OOB ids accumulate where the dense path would), then
    positions holding ``padding_idx`` are routed to the sentinel id
    ``vocab`` — the padding row is never a touched row. Unused slots of
    ``uniq`` are filled with the same sentinel; ``valid`` marks real
    rows. Every downstream consumer drops sentinel rows: the masked
    gather returns zeros for them (which also reproduces the dense
    path's zeroed padding output through ``rows[inv]``), and the
    scatter-apply drops them.
    """
    flat = ids.reshape(-1).astype(jnp.int32)
    clipped = jnp.clip(flat, 0, vocab - 1)
    if padding_idx is not None:
        clipped = jnp.where(flat == padding_idx, vocab, clipped)
    uniq, inv = jnp.unique(clipped, size=flat.shape[0],
                           fill_value=vocab, return_inverse=True)
    return uniq, inv.reshape(ids.shape), uniq < vocab


def segment_sum_rows(grads, inv, num_rows: int):
    """Accumulate per-occurrence row gradients onto their unique row
    (the dedup-side half of a SelectedRows merge_add)."""
    return jax.ops.segment_sum(grads.reshape(-1, grads.shape[-1]),
                               inv.reshape(-1), num_segments=num_rows)


def masked_gather(table, ids, mesh=None, axis: str = "model"):
    """Rows of a row-sharded table; ids outside ``[0, vocab)`` yield
    ZERO rows (no clip) — the sparse path's internal contract: the
    dedup sentinel, padding rows, and hot-cache-hit ids are all routed
    out of bounds to cross the model axis as zeros that cost nothing to
    combine. Without a mesh, the dense single-chip equivalent."""
    vocab = table.shape[0]
    if mesh is None:
        hit = (ids >= 0) & (ids < vocab)
        safe = jnp.clip(ids, 0, vocab - 1)
        got = jnp.take(table, safe, axis=0)
        return jnp.where(hit[..., None], got, jnp.zeros_like(got))
    rows_per = vocab // mesh.shape[axis]

    def local(shard, ids_l):
        my = jax.lax.axis_index(axis)
        loc = ids_l - my * rows_per
        hit = (loc >= 0) & (loc < rows_per)
        safe = jnp.clip(loc, 0, rows_per - 1)
        got = jnp.take(shard, safe, axis=0)
        got = jnp.where(hit[..., None], got, jnp.zeros_like(got))
        return jax.lax.psum(got, axis)

    return shard_map(local, mesh=mesh, in_specs=(P(axis, None), P()),
                     out_specs=P())(table, ids)


def sparse_apply(kind: str, param, slots: Dict[str, jax.Array],
                 uniq, grad_rows, valid, lr, hyper: Dict[str, float],
                 mesh=None, axis: str = "model"
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Apply one sparse optimizer step to the touched rows.

    ``uniq``/``grad_rows``/``valid`` are the replicated dedup outputs
    ([U], [U, D], [U]); ``slots`` holds the per-kind accumulators (row
    slots sharded like the param, scalar slots replicated [1]).
    Returns ``(param_out, slots_out)``. Invalid rows (sentinel fill,
    padding) and rows outside a shard's range are dropped by the
    scatter — their param and slot rows are bit-unchanged.
    """
    if kind not in ROW_SLOTS:
        raise ValueError(f"no sparse rule for optimizer {kind!r}; "
                         f"have {sorted(ROW_SLOTS)}")
    lr = jnp.asarray(lr, param.dtype)
    hyper = dict(SPARSE_HYPER_DEFAULTS[kind], **(hyper or {}))
    b1p = slots.get("beta1_pow")
    b2p = slots.get("beta2_pow")
    row_slot_vals = tuple(slots[s] for s in ROW_SLOTS[kind])
    vocab = param.shape[0]
    n_shards = 1 if mesh is None else mesh.shape[axis]
    rows_per = vocab // n_shards

    # adam's scalar state rides along as [1] replicated operands (a
    # closure over traced values is not portable through shard_map)
    scalars = (b1p, b2p) if kind == "adam" else ()

    def local(p_sh, slot_shs, uniq_, grads_, valid_, lr_, scalars_):
        lo = (0 if mesh is None
              else jax.lax.axis_index(axis) * rows_per)
        loc = uniq_ - lo
        hit = valid_ & (loc >= 0) & (loc < rows_per)
        safe = jnp.clip(loc, 0, rows_per - 1)
        p_rows = jnp.take(p_sh, safe, axis=0)
        s_rows = tuple(jnp.take(s, safe, axis=0) for s in slot_shs)
        b1p_, b2p_ = scalars_ if scalars_ else (None, None)
        new_p, new_s = sparse_row_update(kind, p_rows, s_rows, grads_,
                                         lr_, hyper, b1p_, b2p_)
        tgt = jnp.where(hit, loc, rows_per)   # OOB -> dropped
        p_out = p_sh.at[tgt].set(new_p, mode="drop")
        s_out = tuple(s.at[tgt].set(ns, mode="drop")
                      for s, ns in zip(slot_shs, new_s))
        return p_out, s_out

    if mesh is None:
        p_out, s_out = local(param, row_slot_vals, uniq, grad_rows,
                             valid, lr, scalars)
    else:
        sharded = P(axis, None)
        p_out, s_out = shard_map(
            local, mesh=mesh,
            in_specs=(sharded, tuple(sharded for _ in row_slot_vals),
                      P(), P(), P(), P(),
                      tuple(P() for _ in scalars)),
            out_specs=(sharded, tuple(sharded for _ in row_slot_vals)),
        )(param, row_slot_vals, uniq, grad_rows, valid, lr, scalars)

    slots_out = dict(slots)
    for name, val in zip(ROW_SLOTS[kind], s_out):
        slots_out[name] = val
    if kind == "adam":
        slots_out["beta1_pow"] = b1p * hyper["beta1"]
        slots_out["beta2_pow"] = b2p * hyper["beta2"]
    return p_out, slots_out


def dense_reference_apply(kind: str, param, slots: Dict[str, jax.Array],
                          grad, lr, hyper: Optional[Dict[str, float]]
                          = None):
    """The dense single-chip optimizer step (the exact op formulas,
    applied to the whole table with a dense gradient) — the oracle the
    bit-identity tests compare the sparse path against."""
    hyper = dict(SPARSE_HYPER_DEFAULTS[kind], **(hyper or {}))
    lr = jnp.asarray(lr, param.dtype)
    row_slot_vals = tuple(slots[s] for s in ROW_SLOTS[kind])
    new_p, new_s = sparse_row_update(
        kind, param, row_slot_vals, grad, lr, hyper,
        slots.get("beta1_pow"), slots.get("beta2_pow"))
    slots_out = dict(slots)
    for name, val in zip(ROW_SLOTS[kind], new_s):
        slots_out[name] = val
    if kind == "adam":
        slots_out["beta1_pow"] = slots["beta1_pow"] * hyper["beta1"]
        slots_out["beta2_pow"] = slots["beta2_pow"] * hyper["beta2"]
    return new_p, slots_out
