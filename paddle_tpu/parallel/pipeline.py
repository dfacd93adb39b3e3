"""Pipeline parallelism over a mesh axis (beyond reference parity).

The reference has no pipeline parallelism (SURVEY.md §2 strategy table);
its closest relative is layer-device model parallelism
(ParallelNeuralNetwork.h:34). This module provides the TPU-native
generalization: GPipe-style microbatch pipelining where each device along
the `pipe` mesh axis owns one stage's parameters and activations flow
stage-to-stage over ICI via lax.ppermute inside one lax.scan — the
scaling-book collective-permute pipeline pattern.

Differentiability is free: jax.grad through the scan + ppermute yields
the reversed-permute backward schedule (activations stream backward
through the pipe), so a pipelined loss trains like any other function.
Compose with data parallelism by adding a 'data' mesh axis — the input
microbatches may themselves be batch-sharded.

Constraints (standard for this pattern): every stage maps activations of
one fixed shape to the same shape (transformer-block style), and the
stage count equals the mesh axis size.

Note for CPU-emulated meshes (tests): deep async queues of
collective-permute programs can deadlock the CPU backend's rendezvous —
sync (block_until_ready) between training steps there. Real TPU runtimes
do not have this constraint.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import get_mesh


def pipeline_apply(stage_fn: Callable, stage_params, micro_xs,
                   axis: str = "pipe", mesh: Optional[Mesh] = None,
                   batch_axis: Optional[str] = None):
    """Run `n_micro` microbatches through an `n_stages`-deep pipeline.

    stage_fn: (params_for_one_stage, x) -> y with y.shape == x.shape.
    stage_params: pytree whose leaves have leading dim n_stages (sharded
        over `axis`; leaf i holds stage i's parameters).
    micro_xs: [n_micro, micro_batch, ...] input microbatches
        (replicated along `axis`).
    batch_axis: optional second mesh axis the microbatch dim is sharded
        over (combined DP x PP: each data-parallel row of the mesh runs
        its own pipeline on its batch shard; params stay replicated
        along it).
    Returns [n_micro, micro_batch, ...] outputs of the final stage.

    Schedule: n_micro + n_stages - 1 ticks. At tick t stage 0 ingests
    microbatch t (while t < n_micro), every stage applies its fn to its
    current activation, and activations ppermute one hop down the pipe.
    Bubble overhead is the usual (n_stages-1)/(n_micro+n_stages-1).
    """
    mesh = mesh or get_mesh()
    if mesh is None or axis not in mesh.axis_names:
        raise ValueError(f"pipeline_apply needs a mesh with axis "
                         f"{axis!r} (got {mesh and mesh.axis_names})")
    n_stages = mesh.shape[axis]
    n_micro = micro_xs.shape[0]
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if leaf.shape[0] != n_stages:
            raise ValueError(
                f"stage_params leaf has leading dim {leaf.shape[0]} but "
                f"the {axis!r} mesh axis has {n_stages} stages — each "
                "leaf must hold exactly one slice per stage")
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_stage(params_local, xs_local):
        # params_local leaves: [1, ...] (this stage's slice); drop the
        # stage dim. xs_local: [n_micro, mb, ...] (replicated).
        params_i = jax.tree_util.tree_map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis)
        zero = jnp.zeros_like(xs_local[0])
        # the scan carry is device-varying (each stage holds a different
        # activation): mark the initial value accordingly for shard_map's
        # varying-manual-axes type system
        zero = jax.lax.pcast(zero, (axis,), to="varying")

        def tick(carry, t):
            state = carry            # activation entering this stage
            x_in = jnp.where(
                stage == 0,
                jnp.where(t < n_micro,
                          jax.lax.dynamic_index_in_dim(
                              xs_local, jnp.minimum(t, n_micro - 1), 0,
                              keepdims=False),
                          zero),
                state)
            y = stage_fn(params_i, x_in)
            # activations hop one stage down the pipe; what the last
            # stage sends back to stage 0 is ignored (stage 0 ingests
            # fresh microbatches).
            state_next = jax.lax.ppermute(y, axis, perm)
            # the last stage's y for tick t is microbatch t-(n_stages-1)
            return state_next, y

        ts = jnp.arange(n_micro + n_stages - 1, dtype=jnp.int32)
        _, ys = jax.lax.scan(tick, zero, ts)
        # ys: [ticks, mb, ...]; valid final-stage outputs start at tick
        # n_stages-1. Every stage returns the same-shaped slice; only
        # the last stage's values are meaningful — select afterwards.
        outs = jax.lax.dynamic_slice_in_dim(ys, n_stages - 1, n_micro, 0)
        # broadcast the last stage's outs to all stages so the result is
        # replicated along the pipe axis
        last = n_stages - 1
        outs = jnp.where(stage == last, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    if batch_axis is not None and batch_axis not in mesh.axis_names:
        raise ValueError(f"batch_axis {batch_axis!r} not in mesh axes "
                         f"{mesh.axis_names}")
    xs_spec = P(None, batch_axis) if batch_axis else P()
    return shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), xs_spec),
        out_specs=xs_spec,
    )(stage_params, micro_xs)


def split_microbatches(x, n_micro: int):
    """[batch, ...] -> [n_micro, batch/n_micro, ...]"""
    b = x.shape[0]
    if b % n_micro != 0:
        raise ValueError(f"batch {b} not divisible into {n_micro} "
                         "microbatches")
    return x.reshape((n_micro, b // n_micro) + x.shape[1:])


def merge_microbatches(y):
    """Inverse of split_microbatches."""
    return y.reshape((-1,) + y.shape[2:])
