"""Sharded embedding tables — the TPU-native replacement for the
reference's distributed sparse parameter path.

Reference capability being replaced (SURVEY.md §2 "Sparse/embedding
distribution"): SelectedRows sparse gradients (selected_rows.h:25),
lookup_table with remote prefetch (lookup_table_op.cc, prefetch_op.cc,
split_ids_op.cc), SparseRemoteParameterUpdater
(RemoteParameterUpdater.h:265) and the pserver sparse RPC
(ParameterServer2.h:510). There, huge embedding tables live row-sharded
across parameter servers; trainers fetch only touched rows and push only
touched-row gradients.

TPU-native design: the table is ROW-SHARDED over a mesh axis and stays
on device. Lookup runs under shard_map — each shard gathers the ids that
land in its row range (masked gather, zeros elsewhere) and a psum
combines the one real hit per id across shards, riding ICI instead of
pserver RPC. The backward of that masked gather is a scatter-add into
the local shard only — exactly the SelectedRows "only touched rows
update" semantics, without materializing a dense [V, D] gradient on any
single device. Optimizer state sharded like the table (the
NamedSharding on the param propagates to accumulators) replaces the
pserver-side sparse optimizer.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import get_mesh


def table_spec(axis: str = "model") -> P:
    """PartitionSpec for a row-sharded embedding table [V, D]."""
    return P(axis, None)


def sharded_lookup(table, ids, axis: str = "model",
                   mesh: Optional[Mesh] = None,
                   batch_axis: Optional[str] = None):
    """Gather rows of a row-sharded table: table P(axis, None). Each
    shard answers only ids in its own row range; a psum over `axis`
    assembles the full result. Differentiable — the vjp scatter-adds
    only into the owning shard (SelectedRows-equivalent sparse
    update).

    batch_axis: mesh axis the ids' LEADING dim is sharded over (the
    data-parallel feed axis). When given (and the batch divides it),
    each data row looks up only its own batch shard, so the psum moves
    b_local x D bytes per chip instead of forcing the ids and result
    to be batch-GLOBAL (which made GSPMD all-gather the whole batch
    over the data axis — measured 16.6 MB/step of avoidable traffic
    in the 8-chip DeepFM audit vs 1.3 MB sharded)."""
    mesh = mesh or get_mesh()
    if mesh is None:
        return jnp.take(table, ids, axis=0, mode="clip")
    if axis not in mesh.axis_names:
        raise ValueError(
            f"sharded_lookup axis {axis!r} is not an axis of the active "
            f"mesh {mesh.axis_names}; pass the table's shard axis "
            "explicitly (silent dense fallback would all-gather the "
            "whole table)")
    n_shards = mesh.shape[axis]
    vocab = table.shape[0]
    # match the dense path's jnp.take clip semantics for OOB/negative ids
    ids = jnp.clip(ids, 0, vocab - 1)
    if vocab % n_shards != 0:
        raise ValueError(
            f"vocab size {vocab} must divide evenly over mesh axis "
            f"{axis!r} ({n_shards} shards); pad the table")
    rows_per = vocab // n_shards

    if (batch_axis is not None and batch_axis != axis
            and batch_axis in mesh.axis_names and ids.ndim >= 1
            and ids.shape[0] % mesh.shape[batch_axis] == 0):
        ids_spec = P(batch_axis, *([None] * (ids.ndim - 1)))
        out_spec = P(batch_axis, *([None] * ids.ndim))
    else:
        ids_spec, out_spec = P(), P()

    def local_gather(shard, ids_l):
        # shard: [vocab/n, D]; ids_l: this cell's batch shard
        my = jax.lax.axis_index(axis)
        lo = my * rows_per
        local_ids = ids_l - lo
        hit = (local_ids >= 0) & (local_ids < rows_per)
        safe = jnp.clip(local_ids, 0, rows_per - 1)
        got = jnp.take(shard, safe, axis=0)
        got = jnp.where(hit[..., None], got, jnp.zeros_like(got))
        return jax.lax.psum(got, axis)

    return shard_map(
        local_gather, mesh=mesh,
        in_specs=(P(axis, None), ids_spec),
        out_specs=out_spec,
    )(table, ids)


def shard_table_in_scope(name: str, axis: str = "model",
                         mesh: Optional[Mesh] = None, scope=None):
    """Re-place an existing scope value (a table created by startup)
    onto its row-sharded layout — the moment the reference would
    split_dense_variable a param across pservers
    (distribute_transpiler.py:92)."""
    from ..core.scope import global_scope
    mesh = mesh or get_mesh()
    scope = global_scope() if scope is None else scope
    val = scope.get(name)
    sharded = jax.device_put(val, NamedSharding(mesh, table_spec(axis)))
    scope.set(name, sharded)
    return sharded
