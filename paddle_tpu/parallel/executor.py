"""ParallelExecutor: data/model-parallel program execution over a mesh.

Capability-equivalent of the reference ParallelExecutor + SSA graph +
NCCLAllReduceOpHandle (reference: framework/parallel_executor.cc:46-146,
details/multi_devices_graph_builder.cc:57,
details/nccl_all_reduce_op_handle.cc:30) — redesigned for GSPMD: the feed
batch is sharded over the mesh's 'data' axis, parameters are replicated
(or sharded over 'model' for TP via a sharding spec), and XLA inserts the
gradient all-reduce automatically wherever a reduction crosses the data
axis. One jitted SPMD program replaces per-device op graphs + handles.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import profiler
from ..core.executor import (Executor, CompiledProgram, STEP_SCOPE,
                             by_name, trace_block)
from ..core.lod import RaggedNested, RaggedPair, RaggedTree
from ..core.scope import Scope, global_scope
from .mesh import get_mesh, make_mesh


class ShardingSpec:
    """Per-variable PartitionSpec table — the TPU-native analog of the
    reference DistributeTranspiler's param placement decisions."""

    def __init__(self, specs: Optional[Dict[str, P]] = None,
                 default_param: P = P(), feed_axis: str = "data"):
        self.specs = specs or {}
        self.default_param = default_param
        self.feed_axis = feed_axis

    def param_spec(self, name: str) -> P:
        return self.specs.get(name, self.default_param)

    def feed_spec(self, name: str, ndim: int) -> P:
        if name in self.specs:
            # a ragged feed's companion lengths arrays are lower-rank
            # than its data: truncate the user's spec to this rank so
            # the leading (batch) axes still shard consistently
            spec = tuple(self.specs[name])
            return P(*spec[:ndim])
        if ndim == 0:
            return P()
        return P(self.feed_axis, *([None] * (ndim - 1)))


def _globalize(value, sharding):
    """Multi-process SPMD: lift a process-local value (numpy array, or a
    jax.Array committed to local devices — e.g. params the plain
    Executor initialized from startup) into a global jax.Array laid out
    by `sharding`. The value passed is this process's LOCAL part: the
    full array for dims the sharding replicates across processes, the
    local shard for dims it splits across them (standard per-host
    data-parallel feeding). Already-global arrays pass through."""
    if isinstance(value, jax.Array) and not value.is_fully_addressable:
        return value  # already global
    arr = np.asarray(value)
    return jax.make_array_from_process_local_data(sharding, arr)


class ParallelExecutor(Executor):
    def __init__(self, use_cuda: Optional[bool] = None,
                 loss_name: Optional[str] = None,
                 main_program=None, mesh: Optional[Mesh] = None,
                 sharding: Optional[ShardingSpec] = None, **kw):
        super().__init__()
        self.mesh = mesh or get_mesh() or make_mesh()
        self.sharding = sharding or ShardingSpec()
        self.loss_name = loss_name
        # does the mesh span processes? (multi-host SPMD: feeds/state
        # must be lifted to global arrays before entering the jit)
        self._multiprocess = len(
            {d.process_index for d in self.mesh.devices.flat}) > 1
        self._state_shardings: Dict[str, NamedSharding] = {}
        # globalized read-only state produced inside the compiled call;
        # run() drains it into the run-time scope after each step
        self._pending_ro_globals: Dict[str, Any] = {}

    def state_shardings(self) -> Dict[str, NamedSharding]:
        """Per-state-var NamedShardings from the latest compile —
        exactly what distributed.sharded_checkpoint.load_sharded needs
        to restore this executor's state onto the mesh."""
        return dict(self._state_shardings)

    def run(self, program, feed=None, **kw):
        if self._multiprocess and feed:
            with profiler.RecordEvent("pipeline::globalize_feed",
                                      cat=profiler.CAT_PIPELINE):
                feed = {
                    name: self._globalize_feed(name, v)
                    for name, v in feed.items()}
        self._pending_ro_globals.clear()
        out = super().run(program, feed=feed, **kw)
        if self._pending_ro_globals:
            sc = kw.get("scope") or global_scope()
            for n, g in self._pending_ro_globals.items():
                sc.set(n, g)
            self._pending_ro_globals.clear()
        return out

    def _globalize_feed(self, name, v):
        mesh = self.mesh
        if isinstance(v, RaggedPair):
            return RaggedPair(
                _globalize(v.data, NamedSharding(
                    mesh, self.sharding.feed_spec(name, v.data.ndim))),
                _globalize(v.lengths, NamedSharding(
                    mesh, self.sharding.feed_spec(name, 1))))
        if isinstance(v, RaggedNested):
            return RaggedNested(
                _globalize(v.data, NamedSharding(
                    mesh, self.sharding.feed_spec(name, v.data.ndim))),
                _globalize(v.sub_lengths, NamedSharding(
                    mesh, self.sharding.feed_spec(name, 1))),
                _globalize(v.tok_lengths, NamedSharding(
                    mesh, self.sharding.feed_spec(name, 2))))
        if isinstance(v, RaggedTree):
            return RaggedTree(
                _globalize(v.data, NamedSharding(
                    mesh, self.sharding.feed_spec(name, v.data.ndim))),
                tuple(_globalize(l, NamedSharding(
                    mesh, self.sharding.feed_spec(name, i + 1)))
                    for i, l in enumerate(v.lengths)))
        arr = np.asarray(v)
        return _globalize(arr, NamedSharding(
            mesh, self.sharding.feed_spec(name, arr.ndim)))

    def _compile(self, program, block, feed_sig, fetch_names, scope,
                 while_bounds=None, iterations: int = 1,
                 or_reduce_tail: int = 0, donate: bool = True):
        if iterations != 1:
            raise NotImplementedError(
                "ParallelExecutor does not support run(iterations=K) yet "
                "— the sharded state-threading path would need the scan "
                "carry to preserve NamedShardings. Run steps one at a "
                "time.")
        read_names, write_names = \
            self._state_names(program, block, scope)
        mesh = self.mesh
        # a local, not self: the process-wide list of compiled programs
        # (core/executor.py) keeps `fn` and must not keep this executor
        feed_axis = self.sharding.feed_axis
        fetch_names = list(fetch_names)
        # the jitted step takes its feeds and its state as sequences,
        # each in its names' sorted order (core/executor.py by_name)
        feed_names = [k for k, _ in feed_sig]
        written = set(write_names)
        rw_names = sorted(n for n in read_names if n in written)
        ro_names = sorted(n for n in read_names if n not in written)

        def fn(feed_vals, ro_state, rw_state, step):
            ro_state = by_name(ro_names, ro_state)
            rw_state = by_name(rw_names, rw_state)
            env: Dict[str, Any] = {}
            env.update(ro_state)
            env.update(rw_state)
            env.update(by_name(feed_names, feed_vals))
            extra = {
                "program": program,
                "step": step,
                "mesh": mesh,
                "feed_axis": feed_axis,
                # what shards each state array: a rule that can sum on
                # the shard before a reduction (ops/math_ops.py
                # fanout_mul) reads its weights' specs here
                "state_specs": state_specs,
                "keep_vars": set(fetch_names) | set(write_names),
                "prng": lambda seed: jax.random.fold_in(
                    jax.random.PRNGKey(seed), step),
            }
            if while_bounds:
                extra["while_bounds"] = while_bounds
            env = trace_block(block, env, extra)
            fetches = [env[n] for n in fetch_names]
            # structure must be static (out_shardings is a pytree spec):
            # returnable_names is computed statically below, with the
            # unchanged input as fallback for vars only written inside
            # sub-blocks (which never surface in the parent env)
            new_state = {}
            for n in returnable_names:
                if n in env:
                    new_state[n] = env[n]
                elif n in rw_state:
                    new_state[n] = rw_state[n]
                else:
                    new_state[n] = ro_state[n]
            with jax.named_scope(STEP_SCOPE):
                return fetches, new_state, step + 1

        feed_shardings = {}
        for name, sig in feed_sig:
            if sig[0] == "ragged":
                ndim = len(sig[1])
                feed_shardings[name] = RaggedPair(
                    NamedSharding(mesh, self.sharding.feed_spec(name, ndim)),
                    NamedSharding(mesh, self.sharding.feed_spec(name, 1)))
            elif sig[0] == "ragged2":
                ndim = len(sig[1])
                feed_shardings[name] = RaggedNested(
                    NamedSharding(mesh, self.sharding.feed_spec(name, ndim)),
                    NamedSharding(mesh, self.sharding.feed_spec(name, 1)),
                    NamedSharding(mesh, self.sharding.feed_spec(name, 2)))
            elif sig[0] == "raggedk":
                depth, shape = sig[1], sig[2]
                feed_shardings[name] = RaggedTree(
                    NamedSharding(mesh,
                                  self.sharding.feed_spec(name, len(shape))),
                    tuple(NamedSharding(mesh,
                                        self.sharding.feed_spec(name, i + 1))
                          for i in range(depth)))
            else:
                ndim = len(sig[0])
                feed_shardings[name] = NamedSharding(
                    mesh, self.sharding.feed_spec(name, ndim))
        def state_spec(n):
            """Param spec; optimizer accumulators ({param}_{acc} naming,
            optimizer.py _add_accumulator) follow their param's sharding
            when shape-compatible — a replicated default would clash with
            the GSPMD-propagated sharded outputs on the next call."""
            if n in self.sharding.specs:
                return self.sharding.specs[n]
            best = None
            for p, sp in self.sharding.specs.items():
                if n.startswith(p + "_") and \
                        (best is None or len(p) > len(best[0])):
                    best = (p, sp)
            if best is not None:
                sp = best[1]
                val = scope.find(n)
                shape = None
                if val is not None and hasattr(val, "shape"):
                    shape = val.shape
                else:
                    # not in scope yet (e.g. startup initializing the
                    # accumulator): use the declared var shape so the
                    # very first write already lands sharded
                    v = block.find_var_recursive(n)
                    if v is not None and v.shape and \
                            all(d and d > 0 for d in v.shape):
                        shape = tuple(v.shape)
                if shape is not None and len(shape) == len(sp) and all(
                        ax is None or shape[i] % mesh.shape[ax] == 0
                        for i, ax in enumerate(sp)):
                    return sp
            return self.sharding.default_param

        ro_shardings = {
            n: NamedSharding(mesh, state_spec(n)) for n in ro_names}
        rw_shardings = {
            n: NamedSharding(mesh, state_spec(n)) for n in rw_names}
        state_specs = {n: sh.spec for n, sh in
                       (*ro_shardings.items(), *rw_shardings.items())}
        self._state_shardings.update(ro_shardings)
        self._state_shardings.update(rw_shardings)

        # Input shardings (sharded batch + replicated-or-TP params)
        # determine the SPMD partitioning, including the gradient
        # all-reduce over 'data'. Written-back state is constrained to the
        # SAME shardings as its inputs — otherwise GSPMD-propagated output
        # layouts (e.g. a TP layer's bias picking up 'model') would
        # mismatch the declared in_shardings on the next call.
        # a write_name is returnable iff some parent-block op outputs it
        # or we hold its input value to echo back; vars written only in
        # sub-blocks and never read would have no value to return
        parent_outs = {n for op in block.ops for n in op.output_names()}
        read_set = set(read_names)
        returnable_names = [n for n in write_names
                            if n in parent_outs or n in read_set]
        fetch_out = [None] * len(fetch_names)
        state_out = {n: rw_shardings.get(
            n, NamedSharding(mesh, state_spec(n)))
            for n in returnable_names}
        step_sh = NamedSharding(mesh, P())
        arg_shardings = ([feed_shardings[k] for k in feed_names],
                         [ro_shardings[n] for n in ro_names],
                         [rw_shardings[n] for n in rw_names], step_sh)
        jitted = jax.jit(
            fn, in_shardings=arg_shardings,
            out_shardings=(fetch_out, state_out, step_sh),
            donate_argnums=(2,) if donate else ())

        call = jitted
        if self._multiprocess:
            pending_ro = self._pending_ro_globals

            def call(feed_vals, ro_vals, rw_vals, step):
                # state a plain Executor initialized (startup) lives on
                # local devices; lift it to the global mesh once —
                # thereafter the written-back state is already global.
                # Read-only state is never written back, so its global
                # form is handed to run() via _pending_ro_globals, which
                # writes it into the RUN-TIME scope (one upload, not one
                # per step; the compile-time scope may differ).
                ro = []
                for n, v in zip(ro_names, ro_vals):
                    g = _globalize(v, ro_shardings[n])
                    if g is not v:
                        pending_ro[n] = g
                    ro.append(g)
                rw = [_globalize(v, rw_shardings[n])
                      for n, v in zip(rw_names, rw_vals)]
                return jitted(feed_vals, ro, rw, _globalize(step, step_sh))

        return CompiledProgram(call, read_names, write_names,
                               fetch_names, jitted=jitted,
                               ro_names=ro_names, rw_names=rw_names,
                               block=block, arg_shardings=arg_shardings,
                               feed_names=feed_names)

    @staticmethod
    def _state_names(program, block, scope):
        from ..core.executor import _collect_state_names
        return _collect_state_names(program, block, scope)
