"""Executor: compile a Program block to XLA and run it.

Capability-equivalent of the reference Executor (reference:
paddle/fluid/framework/executor.cc:96-360) with the Prepare/Run split mapped
to trace-compile/execute: instead of interpreting ops one by one and launching
a kernel per op, the whole block is traced into a single pure JAX function
(state-in, state-out over persistable variables) and jit-compiled once per
(program version, feed signature). XLA then fuses across op boundaries —
the TPU-native answer to the reference's per-op kernel dispatch.

Parameter updates (optimizer ops writing `ParamOut` to the parameter name)
become functional state threading with buffer donation, so updates are
in-place on device just like the reference's in-place kernels.
"""
from __future__ import annotations

import atexit
import os
import threading
import time
import warnings
import weakref
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.monitoring
import jax.numpy as jnp

from ..amp import amp_enabled
from .. import profiler
from ..observability import trace as obs_trace
from ..observability.registry import default_registry
from . import op_table
from .ir import Program, BlockDesc, OpDesc, SUB_BLOCK_ATTRS
from .lod import LoDTensor, RaggedNested, RaggedPair, RaggedTree
from .registry import OpRegistry, run_op
from .scope import Scope, global_scope

STEP_VAR = "@step_counter@"
# the named scope of the one instruction of a compiled step that is no
# op of the program: the step counter's advance
STEP_SCOPE = "step_counter"

# JAX's persistent compilation cache lives at a FIXED path inside the
# checkout (derived from this package's own location, like native.py):
# the directory is part of the cache key, so one that moves — a
# tempdir, a pid, a timestamp — never hits.
_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_compile_cache() -> Optional[str]:
    """Place JAX's persistent compilation cache before the first
    compile and return the directory in use. ``JAX_COMPILATION_CACHE_DIR``
    set from outside wins (JAX reads it itself; nothing is set in
    code); otherwise the cache goes to ``<checkout>/.jax_cache``.
    Idempotent — every Executor construction calls it, and it is the
    only place in the tree that sets a cache directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR") and \
            jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", _COMPILE_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir

# Donate the read-write persistable state (params + optimizer
# accumulators) to the jitted step so XLA aliases state-in to state-out
# instead of allocating a fresh output buffer per step. On by default;
# PADDLE_TPU_DONATE_STATE=0 (or Executor(donate_state=False)) restores
# copy-per-step for callers that hold references to scope state across
# runs. Part of the compile-cache key: flipping it recompiles.
DONATE_STATE_DEFAULT = \
    os.environ.get("PADDLE_TPU_DONATE_STATE", "1") != "0"

# Parity with the reference's FLAGS_check_nan_inf (executor.cc:27,345-353).
CHECK_NAN_INF = os.environ.get("PADDLE_TPU_CHECK_NAN_INF", "0") == "1"
# A bounded While that hit max_steps with its condition still true warns
# once per (program, flag) by default; PADDLE_TPU_CHECK_WHILE_BOUND=1
# raises instead.
CHECK_WHILE_BOUND = \
    os.environ.get("PADDLE_TPU_CHECK_WHILE_BOUND", "0") == "1"
_WARNED_WHILE_FLAGS: set = set()


def _check_while_flag(key, value, raise_: bool):
    """key = (program uid, flag var name); value = the fetched bool."""
    if not bool(np.asarray(value).reshape(-1)[0]):
        return
    msg = (f"bounded While loop flag {key[1]!r}: the loop hit max_steps "
           "with its condition still true — it was truncated; raise "
           "max_steps")
    if raise_:
        raise RuntimeError(msg)
    if key not in _WARNED_WHILE_FLAGS:
        _WARNED_WHILE_FLAGS.add(key)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


# Device-side cache for immutable feed arrays: the same batch is
# uploaded once, not per run. Only arrays that OWN their buffer and are
# frozen (arr.flags.writeable = False) are cached by identity: a
# read-only VIEW (e.g. np.broadcast_to, or a frozen slice) can still
# change through its writeable base, which would silently serve stale
# device data. Freezing an owning array is the caller's immutability
# contract. DataFeeder freezes its outputs when constructed with
# freeze=True.
_feed_cache: Dict[int, Tuple[Any, Any]] = {}
_FEED_CACHE_MAX = int(os.environ.get("PADDLE_TPU_FEED_CACHE_MAX", "8"))
# The cache is shared process-wide and executors now run from multiple
# threads (serving workers co-resident with a training loop), so the
# pop/re-insert LRU dance and eviction must be atomic.
_feed_cache_lock = threading.Lock()


def _cached_device_put(arr: np.ndarray):
    key = id(arr)
    with _feed_cache_lock:
        hit = _feed_cache.get(key)
        if hit is not None and hit[0]() is arr:
            # LRU: re-insert on hit so steady reuse (e.g. a validation
            # batch fed every step alongside rotating train batches) is
            # never the eviction victim just because it was inserted
            # first.
            _feed_cache.pop(key, None)
            _feed_cache[key] = hit
            return hit[1]
    dev = jnp.asarray(arr)
    try:
        ref = weakref.ref(arr, lambda _r, k=key: _feed_cache.pop(k, None))
        with _feed_cache_lock:
            # Bounded: evict least-recently-used (dicts iterate in
            # insertion order; hits re-insert) so an epoch of
            # precomputed frozen batches can't pin one device copy per
            # batch for the epoch's lifetime.
            while len(_feed_cache) >= _FEED_CACHE_MAX:
                _feed_cache.pop(next(iter(_feed_cache)))
            _feed_cache[key] = (ref, dev)
    except TypeError:
        pass
    return dev


def _maybe_cached(arr):
    """Frozen owned ndarrays go through the device-side feed cache so a
    repeated identical batch is uploaded once, not per step."""
    if isinstance(arr, np.ndarray) and not arr.flags.writeable \
            and arr.flags.owndata:
        return _cached_device_put(arr)
    return jnp.asarray(arr)


def _to_device_value(value):
    """Convert a feed value (numpy / LoDTensor / scalar) to in-graph form."""
    if isinstance(value, RaggedPair):
        # cache ragged components too — otherwise every step re-uploads
        # the padded batch over the host link
        return RaggedPair(_maybe_cached(value.data),
                          _maybe_cached(value.lengths))
    if isinstance(value, RaggedNested):
        return RaggedNested(_maybe_cached(value.data),
                            _maybe_cached(value.sub_lengths),
                            _maybe_cached(value.tok_lengths))
    if isinstance(value, RaggedTree):
        return RaggedTree(_maybe_cached(value.data),
                          tuple(_maybe_cached(l) for l in value.lengths))
    if isinstance(value, LoDTensor):
        if len(value.lod) > 2:
            # arbitrary-depth LoD (lod_tensor.h:55-107): dense padded
            # tree + per-level length arrays
            data, lengths = value.to_tree_padded()
            return RaggedTree(jnp.asarray(data),
                              tuple(jnp.asarray(l) for l in lengths))
        if len(value.lod) == 2:
            data, sub_l, tok_l = value.to_nested_padded()
            return RaggedNested(jnp.asarray(data), jnp.asarray(sub_l),
                                jnp.asarray(tok_l))
        if value.lod:
            padded, lengths = value.to_padded()
            return RaggedPair(jnp.asarray(padded), jnp.asarray(lengths))
        value = value.data
    return _maybe_cached(value)


_DTYPE_NAMES: Dict[Tuple, str] = {}


def _dtype_name(dtype) -> str:
    """The name of the dtype an array of `dtype` has once on the device
    (`jnp.asarray`'s canonical form: int64 -> int32 with x64 off)."""
    key = (dtype, jax.config.jax_enable_x64)
    name = _DTYPE_NAMES.get(key)
    if name is None:
        name = _DTYPE_NAMES[key] = str(jax.dtypes.canonicalize_dtype(dtype))
    return name


def _feed_arg(value):
    """(a feed value as the jitted step takes it, its `_abstractify`).
    A plain host array goes as it is: the jitted call's own argument
    path uploads it, with the same canonical dtype and without
    `jnp.asarray`'s Python layers in front. Everything else (a frozen
    owning array, which the feed cache holds on the device; ragged and
    LoD feeds; scalars) goes through `_to_device_value` as before."""
    if type(value) is np.ndarray:
        as_it_is = value.flags.writeable or not value.flags.owndata
    else:
        as_it_is = isinstance(value, jax.Array)
    if not as_it_is:
        value = _to_device_value(value)
    return value, _abstractify(value)


def device_feed(feed: Dict[str, Any]) -> Dict[str, Any]:
    """Upload a host feed dict to in-graph device form (idempotent:
    already-device values pass through). The shared convert+upload step
    behind DataFeeder.feed_device and the Trainer's feed prefetch."""
    return {k: _to_device_value(v) for k, v in feed.items()}


def _np_fetch(x) -> np.ndarray:
    """Device -> numpy, widening bf16 to f32 at the fetch boundary: under
    AMP activations live on device at half width, but numpy has no native
    bfloat16 and the user-facing contract stays float32."""
    arr = np.asarray(x)
    if arr.dtype.name == "bfloat16":
        arr = arr.astype(np.float32)
    return arr


def _to_host_value(value, return_numpy: bool):
    if isinstance(value, RaggedPair):
        padded = _np_fetch(value.data)
        lengths = np.asarray(value.lengths)
        return LoDTensor.from_padded(padded, lengths)
    if isinstance(value, RaggedNested):
        return LoDTensor.from_nested_padded(
            _np_fetch(value.data), np.asarray(value.sub_lengths),
            np.asarray(value.tok_lengths))
    if isinstance(value, RaggedTree):
        return LoDTensor.from_tree_padded(
            _np_fetch(value.data),
            [np.asarray(l) for l in value.lengths])
    return _np_fetch(value) if return_numpy else value


def _abstractify(value):
    if isinstance(value, RaggedPair):
        return ("ragged", value.data.shape, str(value.data.dtype),
                value.lengths.shape)
    if isinstance(value, RaggedNested):
        return ("ragged2", value.data.shape, str(value.data.dtype),
                value.tok_lengths.shape)
    if isinstance(value, RaggedTree):
        return ("raggedk", len(value.lengths), value.data.shape,
                str(value.data.dtype))
    return (tuple(value.shape), _dtype_name(value.dtype))


def feed_signature(feed_vals) -> Tuple:
    """Hashable (name, abstract shape/dtype) signature of a feed dict —
    the per-request part of the executor's compile-cache key. Feed values
    must already be in device form (`_to_device_value`); plain
    numpy/ndarray-likes with .shape/.dtype also work. Serving uses this
    to predict whether a padded batch will reuse an existing executable."""
    return tuple(sorted((k, _abstractify(v)) for k, v in feed_vals.items()))


class StepResult:
    """Undelivered fetches of an async `Executor.run(..., sync=False)`.

    Holds the dispatched step's device values; nothing blocks until a
    fetched value is consumed. `fetches()` (and indexing/iteration)
    materializes host values once, under a `pipeline::fetch_sync`
    profiler event, then drops the device references so the buffers are
    not pinned for the result's lifetime. `block_until_ready()` waits
    for the computation without converting. XLA async errors (and the
    NaN/Inf check, when enabled) surface at materialization, not at
    dispatch."""

    def __init__(self, raw_fetches, fetch_names, return_numpy: bool,
                 nan_check: bool = False, trace_ctx=None):
        self._raw = list(raw_fetches)
        self.fetch_names = list(fetch_names)
        self._return_numpy = return_numpy
        self._nan_check = nan_check
        # the step span active at dispatch: lazy materialization stamps
        # its fetch_sync event with the OWNING step's ids even when it
        # runs under a later step's span (or none) — see trace.use_span
        self._trace_ctx = trace_ctx
        self._values: Optional[List[Any]] = None
        #: static ProgramCost of the executable this dispatch ran
        #: (set by Executor.run; None when the cost pass failed)
        self.cost = None

    @property
    def ready(self) -> bool:
        """True once the dispatched step has finished on device (always
        True after materialization)."""
        if self._values is not None:
            return True
        return all(leaf.is_ready() for leaf
                   in jax.tree_util.tree_leaves(self._raw)
                   if hasattr(leaf, "is_ready"))

    def block_until_ready(self) -> "StepResult":
        """Wait for the device computation; does NOT convert to host."""
        if self._values is None:
            for leaf in jax.tree_util.tree_leaves(self._raw):
                if hasattr(leaf, "block_until_ready"):
                    leaf.block_until_ready()
        return self

    def fetches(self) -> List[Any]:
        """Materialized fetch values (cached after the first call)."""
        if self._values is None:
            with obs_trace.use_span(self._trace_ctx):
                with profiler.RecordEvent("pipeline::fetch_sync",
                                          cat=profiler.CAT_PIPELINE):
                    vals = [_to_host_value(v, self._return_numpy)
                            for v in self._raw]
            if self._nan_check:
                for n, v in zip(self.fetch_names, vals):
                    arr = v.data if isinstance(v, LoDTensor) else v
                    if np.issubdtype(np.asarray(arr).dtype, np.floating) \
                            and not np.isfinite(arr).all():
                        err = FloatingPointError(
                            f"NaN/Inf detected in fetched var {n!r}")
                        # flight-recorder trigger: the dump holds the
                        # events leading up to the poisoned step
                        from ..observability.flight_recorder import \
                            record_failure
                        record_failure("nan_fetch", exc=err,
                                       context={"var": n})
                        raise err
            self._values = vals
            self._raw = []  # release device references
        return list(self._values)

    def __len__(self):
        return len(self.fetch_names)

    def __getitem__(self, i):
        return self.fetches()[i]

    def __iter__(self):
        return iter(self.fetches())


def _wiring_key(type: str, inputs: Dict, outputs: Dict):
    """What a forward op and the copy its grad op embeds share: the
    type and the input and output wiring."""
    return (type,
            tuple(sorted((s, tuple(ns)) for s, ns in inputs.items())),
            tuple(sorted((s, tuple(ns)) for s, ns in outputs.items())))


def _vjp_sites(ops) -> Optional[Dict[Any, OpDesc]]:
    """{_wiring_key of a forward op: the __vjp__ op of `ops` that embeds
    it} for the grad ops that may reuse their forward op's pullback
    (attr drift tolerated here; run_op_keeping_pullback checks the
    embedded attrs). A grad op fed another name than its forward op read
    (backward.py's @PRE. snapshots) is no site, nor are two grad ops of
    one key. None where `ops` holds no __vjp__."""
    sites: Optional[Dict[Any, Optional[OpDesc]]] = None
    for op in ops:
        if op.type != "__vjp__":
            continue
        if sites is None:
            sites = {}
        fwd = op.attrs["fwd_op"]
        read = [n for names in fwd["inputs"].values() for n in names]
        read += op.attrs.get("closure_names") or []
        if op.inputs.get("FwdIn") != read:
            continue
        key = _wiring_key(fwd["type"], fwd["inputs"], fwd["outputs"])
        sites[key] = None if key in sites else op
    return sites


def trace_block(block: BlockDesc, env: Dict[str, Any],
                extra: Dict[str, Any]) -> Dict[str, Any]:
    """Run every op's compute rule under trace, mutating env. Returns env.

    Ops annotated by the memory-optimization transpiler carry a
    __dead_vars__ attr (transpiler/memory_optimization_transpiler.py):
    those tracers are dropped from env right after the op, shortening
    tracer lifetimes (XLA does in-executable buffer reuse on its own;
    this keeps the lowering from pinning dead values). Vars in
    extra["keep_vars"] (fetches + state writes) always survive.

    A forward op whose __vjp__ op comes later in THIS block runs once,
    under jax.vjp, and its pullback waits for that grad op in a dict this
    call puts under extra[VJP_PULLBACKS] and takes away again on its way
    out (an exception's too): a pullback never outlives the call that made
    it, and a sub-block or scan-body trace, a call of its own, sees only
    its own. A block without a __vjp__ op is traced with no such dict."""
    sites = _vjp_sites(block.ops)
    if not sites:
        return _trace_ops(block, env, extra, None)
    from ..ops.core_ops import VJP_PULLBACKS
    outer = extra.get(VJP_PULLBACKS)
    extra[VJP_PULLBACKS] = {}
    try:
        return _trace_ops(block, env, extra, sites)
    finally:
        if outer is None:
            del extra[VJP_PULLBACKS]
        else:
            extra[VJP_PULLBACKS] = outer


def _block_path(block) -> Tuple[int, ...]:
    """A block's indices from its root down to itself: the `block_path`
    of the cost model's rows (analysis/passes.py iter_blocks)."""
    path = [block.idx]
    while block.parent_idx >= 0:
        block = block.program.blocks[block.parent_idx]
        path.append(block.idx)
    return tuple(reversed(path))


def _trace_ops(block, env, extra, sites):
    if sites:
        from ..ops.core_ops import run_op_keeping_pullback
    keep = extra.get("keep_vars") or ()
    stats = extra.get("trace_stats")  # optional {.. -> peak_env_bytes}
    # inside a static_rnn's body: names what the loop keeps of a pass
    kept_outputs = extra.get("kept_outputs")
    path = _block_path(block)
    for index, op in enumerate(block.ops):
        # the op type (for a grad op, its forward op's too) and then
        # the op itself, as cost_model.OpCost names it (block path and
        # index), in the op_name of every HLO instruction the rule
        # emits: "jit(step_fn)/matmul/b0.412/dot_general", which
        # core/op_table.py reads back out of the compiled module. Trace
        # time only, and a function of the program's content alone: a
        # uid or a counter here would change the HLO from process to
        # process and the persistent compile cache would never hit. An
        # inner jax.jit that several sites share (the flash kernels,
        # the twelve kv_cache_append sites) is lowered once and keeps
        # its FIRST site's index: rows by type are exact, rows by op
        # put every site's time on the first
        fwd = op.attrs.get("fwd_op") if op.type == "__vjp__" else None
        with jax.named_scope(f"__vjp__.{fwd['type']}" if fwd
                             else op.type), \
                jax.named_scope(op_table.scope(path, index)):
            gop = sites and sites.get(
                _wiring_key(op.type, op.inputs, op.outputs))
            outs = gop and run_op_keeping_pullback(op, gop, env, extra)
            if outs is None:
                outs = run_op(op, env, extra)
            env.update(kept_outputs(op, outs) if kept_outputs else outs)
        dead = op.attrs.get("__dead_vars__")
        if dead:
            for name in dead:
                if name not in keep:
                    env.pop(name, None)
        if stats is not None:
            live = 0
            for v in env.values():
                size = getattr(v, "size", None)
                dt = getattr(v, "dtype", None)
                if size is not None and dt is not None:
                    live += int(size) * np.dtype(dt).itemsize
            stats["peak_env_bytes"] = max(
                stats.get("peak_env_bytes", 0), live)
    return env


def _collect_state_names(program: Program, block: BlockDesc,
                         scope: Scope) -> Tuple[List[str], List[str]]:
    """Names of persistable vars this block reads (from scope) and writes."""
    reads, writes = [], []
    seen_r, seen_w = set(), set()

    def visit(blk: BlockDesc):
        for op in blk.ops:
            for name in op.input_names():
                v = blk.find_var_recursive(name)
                if v is not None and v.persistable and name not in seen_r:
                    seen_r.add(name)
                    reads.append(name)
            for name in op.output_names():
                v = blk.find_var_recursive(name)
                if v is not None and v.persistable and name not in seen_w:
                    seen_w.add(name)
                    writes.append(name)
            for attr in SUB_BLOCK_ATTRS:
                idx = op.attrs.get(attr)
                if isinstance(idx, int) and 0 <= idx < len(program.blocks):
                    visit(program.blocks[idx])

    visit(block)
    # Only read state that actually exists in scope (written-only vars like
    # freshly initialized params have no prior value).
    reads = [n for n in reads if scope.has(n)]
    return reads, writes


class CompiledProgram:
    """A jitted artifact for (program, feed signature, fetch list).

    `fn(feeds, ro, rw, step)` takes SEQUENCES: the feeds in
    `feed_names`' order, the state `ro_names` and `rw_names` name, each
    sorted by name (the order a dict by name flattens to), and returns
    (fetches, {name: written state}, the step counter advanced).
    `jitted` is the jax.jit stage under it, for AOT introspection
    (profiler.cost_analysis, HLO dumps): it takes the same sequences
    or, for a caller that holds names, dicts by name; `lower_again` is
    the one way to it, and `op_table` what is read from it most."""

    def __init__(self, fn, read_names, write_names, fetch_names,
                 jitted=None, ro_names=(), rw_names=(), block=None,
                 arg_shardings=None, feed_names=()):
        self.fn = fn
        self.read_names = read_names
        self.write_names = write_names
        self.fetch_names = fetch_names
        self.jitted = jitted
        self.feed_names = list(feed_names)
        self.ro_names = list(ro_names)
        self.rw_names = list(rw_names)
        # the donated names the trace does not write back, which a
        # commit erases from the scope: known from the first call's
        # result on (the traced step's outputs are static)
        self.unwritten: Optional[List[str]] = None
        # the block `jitted` traces (its closure holds it anyway)
        self.block = block
        # the mesh executor's in_shardings, as (feed, ro, rw, step)
        self.arg_shardings = arg_shardings
        # static ProgramCost of ONE traced iteration, attached by
        # Executor.run at the compile-cache miss that built this
        # executable (None when the cost model could not run)
        self.cost = None
        # static MemoryReport of the traced program, attached next to
        # the cost (None when the planner could not run)
        self.memory = None
        # abstract values of `jitted`'s arguments (ShapeDtypeStructs
        # with shardings: no device memory), recorded at that miss
        self.avals = None
        self._op_table = None

    def record_avals(self, *args) -> None:
        """Keep the abstract form of the arguments (feeds, ro, rw, step)
        of the call about to be made, so that `lower_again` needs
        neither a scope nor an executor nor a feed. An array keeps the
        sharding it is committed to (the mesh executor's declared ones
        where it compiled)."""
        shardings = () if self.arg_shardings is None \
            else (self.arg_shardings,)
        self.avals = jax.tree_util.tree_map(_aval, args, *shardings)

    def lower_again(self):
        """The compiled executable of this entry, lowered again from the
        recorded abstract values: THE implementation of "lower a cache
        entry again" (`parallel.collective_audit.aot_compiled_for` is a
        look-up in front of it). JAX re-uses the jaxpr it traced for the
        same abstract values, so no op rule runs again, and the
        persistent compile cache answers the compile where it is on."""
        if self.avals is None:
            raise RuntimeError(
                "no recorded arguments for AOT lowering: this entry was "
                "not compiled through Executor.run")
        return self.jitted.lower(*self.avals).compile()

    def op_table(self):
        """{instruction name: (op type, role, block path, op index)} of
        the compiled step and what goes with it (core/op_table.py).
        Built on the first ask, never before, and kept as plain data."""
        if self._op_table is None:
            self._op_table = op_table.parse(
                self.lower_again().as_text(), self.program_ops())
        return self._op_table

    @property
    def uid(self) -> Optional[int]:
        """The uid of the Program traced (this process's own number for
        it: it names rows for a reader and never reaches the HLO)."""
        return None if self.block is None else self.block.program.uid

    def program_ops(self):
        """{(block idx, op index): (role, scope type)} of the program
        this entry traces (`op_table.program_ops`)."""
        return op_table.program_ops(self.block)


def by_name(names, values) -> Dict[str, Any]:
    """{name: value} of one argument of a jitted step: handed over as a
    sequence in `names`' order (Executor.run) or, by a caller that holds
    the names, as that dict already."""
    return values if isinstance(values, dict) else dict(zip(names, values))


def _aval(x, sharding=None):
    if sharding is None and isinstance(x, jax.Array) and x.committed:
        sharding = x.sharding
    return jax.ShapeDtypeStruct(
        np.shape(x), jax.dtypes.canonicalize_dtype(np.result_type(x)),
        sharding=sharding, weak_type=getattr(x, "weak_type", False))


# Every step program compiled in this process, newest last: a reader
# that holds no executor (a telemetry thread, the benchmark after its
# driver has closed its executor) asks here. Entries hold the jitted
# stage and plain data, never state arrays; the oldest fall out.
_COMPILED_MAX = 16
_compiled_programs: "deque[CompiledProgram]" = deque(
    maxlen=_COMPILED_MAX)
_compiled_lock = threading.Lock()


def compiled_programs() -> List[CompiledProgram]:
    """The cache entries of every Executor of this process (the last
    16), whether or not that executor is still open."""
    with _compiled_lock:
        return list(_compiled_programs)


class _BlockPrefix:
    """A view of a block truncated to its first `n` ops (the executor's
    WhileGrad probe traces only the forward prefix up to the last
    dynamic While)."""

    def __init__(self, block: BlockDesc, n: int):
        self._block = block
        self.ops = list(block.ops[:n])

    def __getattr__(self, name):
        return getattr(self._block, name)


def _dynamic_while_targets(block: BlockDesc):
    """{while_id: steps_var_name} for every unbounded While a __vjp__
    grad op replays — directly, or NESTED inside a replayed While /
    DynamicRNN / StaticRNN (their ops max-accumulate nested trip counts
    into NestedSteps outputs; reference analog: while_op.cc:96 step
    scopes nest freely) — plus the index one past the last such forward
    op, the probe prefix length."""
    def op_key(t, attrs):
        if t == "while":
            return ("while", attrs.get("while_id"))
        if t in ("dynamic_rnn", "static_rnn"):
            return (t, attrs.get("sub_block_idx"))
        if t in ("cond", "if_else"):
            return (t, attrs.get("true_block_idx"),
                    attrs.get("false_block_idx"))
        return None

    grad_keys = set()
    for op in block.ops:
        if op.type != "__vjp__":
            continue
        fwd = op.attrs.get("fwd_op") or {}
        key = op_key(fwd.get("type"), fwd.get("attrs") or {})
        if key is not None:
            grad_keys.add(key)
    if not grad_keys:
        return {}, 0
    targets, prefix = {}, 0
    for i, op in enumerate(block.ops):
        key = op_key(op.type, op.attrs)
        if key is None or key not in grad_keys:
            continue
        found = False
        if op.type == "while" and op.attrs.get("dynamic_bound") and \
                int(op.attrs.get("max_steps", 0) or 0) <= 0:
            steps = op.outputs.get("Steps")
            if not steps:
                raise RuntimeError(
                    f"dynamic While {op.attrs.get('while_id')!r} has no "
                    "Steps output — rebuild the program with the "
                    "current While layer")
            targets[op.attrs["while_id"]] = steps[0]
            found = True
        nested = op.attrs.get("nested_while_ids") or []
        if nested:
            ns_vars = op.outputs.get("NestedSteps") or []
            if len(ns_vars) != len(nested):
                raise RuntimeError(
                    f"{op.type} op has nested dynamic Whiles {nested} "
                    "but no matching NestedSteps outputs — rebuild the "
                    "program with the current control-flow layers")
            targets.update(zip(nested, ns_vars))
            found = True
        if found:
            prefix = i + 1
    return targets, prefix


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length() if n > 1 else 1


def _stateful_ops_in(program: Program, ops) -> List[str]:
    """Op types with host-side effects (ordered io_callback: channel
    send/recv, select, go, ...) reachable from `ops`, including
    sub-blocks. The WhileGrad probe re-executes its forward prefix, so a
    stateful op there would fire twice per step — desyncing channel
    protocols. Detected and rejected rather than silently doubled."""
    found: List[str] = []

    def visit(op_list):
        for op in op_list:
            if OpRegistry.has(op.type) and OpRegistry.get(op.type).stateful:
                found.append(op.type)
            for attr in SUB_BLOCK_ATTRS:
                idx = op.attrs.get(attr)
                if isinstance(idx, int) and 0 <= idx < len(program.blocks):
                    visit(program.blocks[idx].ops)

    visit(ops)
    return found


# Process-wide executor metrics, resolved lazily against the CURRENT
# default registry (identity-checked per call so a registry swap —
# tests, the telemetry-overhead benchmark — takes effect on the next
# run() without re-importing). Aggregated across executors: the scrape
# answers "how much compilation is this process paying", which is the
# capacity question; per-executor splits stay on Executor.cache_stats.
_obs_cache = None
_compile_obs_cache = None


def _compile_instruments():
    """(registry, phase-seconds counter family, persistent-cache hits,
    persistent-cache misses): what a recompile cost and whether JAX's
    persistent cache answered it, on /metrics."""
    global _compile_obs_cache
    reg = default_registry()
    if _compile_obs_cache is None or _compile_obs_cache[0] is not reg:
        _compile_obs_cache = (
            reg,
            reg.counter(
                "paddle_tpu_compile_phase_seconds_total",
                "Host seconds spent compiling, by phase: verify, "
                "memory_plan, cost_model (this program's analyses on "
                "an executor compile-cache miss) and "
                "jax_trace, lower, backend, cache_retrieval (JAX's own "
                "compile events; nested events of one phase are "
                "counted once).", ("phase",)),
            reg.counter(
                "paddle_tpu_persistent_cache_hits_total",
                "Compiles answered by JAX's persistent compilation "
                "cache (its cache_hits event)."),
            reg.counter(
                "paddle_tpu_persistent_cache_misses_total",
                "Compiles JAX's persistent compilation cache did not "
                "hold and wrote (its cache_misses event)."),
        )
    return _compile_obs_cache


# compile::<phase> of each JAX duration event that is a compile phase
_JAX_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace",
    # StableHLO lowering, the Mosaic kernels' included
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # XLA, or the persistent cache's retrieval where it hits
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
# JAX event -> its counter's place in _compile_instruments()
_JAX_CACHE_COUNTERS = {"/jax/compilation_cache/cache_hits": 2,
                       "/jax/compilation_cache/cache_misses": 3}
# {"uid", "block"} of the program whose first dispatch this thread is
# in (Executor.run), else None
_compiling = threading.local()
# per thread, per phase: the (start, seconds) already counted, newest
# last. Events of one thread arrive ordered by their END, so the ones a
# new event encloses are at the tail.
_counted = threading.local()
_COUNTED_MAX = 4096


def _count_compile_event(ev: Dict[str, Any]) -> None:
    """profiler listener: add to paddle_tpu_compile_phase_seconds_total
    what a closed compile::<phase> span adds to the union of its
    thread's spans of that phase (an inner jit fires its own event
    inside the outer one's, and closes first)."""
    if ev.get("cat") != profiler.CAT_COMPILE:
        return
    phase = ev["name"].partition("::")[2]
    start, dur = ev["ts"] * 1e-6, ev["dur"] * 1e-6
    stacks = getattr(_counted, "stacks", None)
    if stacks is None:
        stacks = _counted.stacks = {}
    stack = stacks.get(phase)
    if stack is None:
        stack = stacks[phase] = deque(maxlen=_COUNTED_MAX)
    inside = 0.0
    while stack and stack[-1][0] >= start:
        inside += stack.pop()[1]
    stack.append((start, dur))
    _compile_instruments()[1].labels(phase=phase).inc(
        max(0.0, dur - inside))


def _compile_span(phase: str, args: Dict[str, Any]):
    """compile::<phase> around one of this program's own analyses."""
    return profiler.RecordEvent("compile::" + phase,
                                cat=profiler.CAT_COMPILE, args=args)


def _on_jax_duration(event: str, secs: float, **kw) -> None:
    """The one jax.monitoring duration listener: each compile phase JAX
    reports becomes a closed compile::<phase> span that ends now."""
    phase = _JAX_COMPILE_PHASES.get(event)
    if phase is None:
        return
    args = dict(getattr(_compiling, "args", None) or {})
    if "fun_name" in kw:
        args["fun_name"] = kw["fun_name"]
    profiler.emit("compile::" + phase, time.perf_counter() - secs, secs,
                  profiler.CAT_COMPILE, args)


def _on_jax_event(event: str, **_kw) -> None:
    which = _JAX_CACHE_COUNTERS.get(event)
    if which is not None:
        _compile_instruments()[which].inc()


_listeners_lock = threading.Lock()
_listening = False


def _listen_to_compiles() -> None:
    """Install the three listeners once a process (jax.monitoring keeps
    its two for good); called by every Executor construction."""
    global _listening
    with _listeners_lock:
        if not _listening:
            profiler.add_event_listener(_count_compile_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration)
            jax.monitoring.register_event_listener(_on_jax_event)
            _listening = True


def _obs_instruments():
    """(registry, hits, misses, the donation gauge, {outcome: bound
    steps}): the series themselves, resolved once a registry — a call
    of run() increments two and sets one."""
    global _obs_cache
    reg = default_registry()
    if _obs_cache is None or _obs_cache[0] is not reg:
        _obs_cache = (
            reg,
            reg.counter(
                "paddle_tpu_compile_cache_hits_total",
                "Executor.run dispatches served by an already-jitted "
                "executable (all executors in this process).").labels(),
            reg.counter(
                "paddle_tpu_compile_cache_misses_total",
                "Executor.run dispatches that traced + XLA-compiled a "
                "new executable (all executors in this process).").labels(),
            reg.gauge(
                "paddle_tpu_executor_donate_state",
                "1 when the most recent Executor.run dispatched with "
                "donated (buffer-aliased) train state, else 0.").labels(),
        )
        bound = reg.counter(
            "paddle_tpu_executor_bound_steps_total",
            "Executor.run calls by what resolved them: hit (a record "
            "of this program and feed signature served the call) or "
            "bound (the record was built: a first call, a new feed "
            "signature, a program whose version moved). A steady loop "
            "reads hits only.", ("outcome",))
        _obs_cache += ({"hit": bound.labels(outcome="hit"),
                        "bound": bound.labels(outcome="bound")},)
    return _obs_cache


class _Binding:
    """What `Executor.run` resolved once for a program AS IT IS CALLED
    (uid, version, block, fetch list, iterations, stacked_feed, sync,
    donation, ambient AMP and verification) — the reference's
    `Executor::Prepare` beside its `RunPreparedContext`
    (executor.cc:271-360): everything a call used to recompute from the
    block's ops. It holds NAMES and plain data, never an array: what a
    step reads is what the scope holds when it is called."""

    __slots__ = ("fetch_names", "n_user_fetches", "gated", "probe",
                 "stateful", "steps")

    def __init__(self, block: BlockDesc, user_fetches: List[str]):
        # Auto-fetch every bounded-While exhaustion flag in this block
        # (plain temps, not persistable state). Appended even when the
        # user also fetches one — the checked tail must be complete.
        # Truncation warns once per flag by default; with
        # PADDLE_TPU_CHECK_WHILE_BOUND=1 it raises instead. Limitation:
        # a bounded While nested inside another sub-block keeps its flag
        # block-local; propagate it to a parent var (assign) to check it
        # here.
        self.fetch_names = user_fetches + [
            op.outputs["Exhausted"][0] for op in block.ops
            if op.type == "while" and op.outputs.get("Exhausted")]
        self.n_user_fetches = len(user_fetches)
        # the feed-name tuples the verifier's gate has passed with
        self.gated: set = set()
        # (targets, prefix) of `_dynamic_while_targets`, () where the
        # block has none, None until a call has looked
        self.probe: Optional[Tuple] = None
        # `_stateful_ops_in` the block, None until iterations > 1 asks
        self.stateful: Optional[List[str]] = None
        # {the feeds' names, shapes and dtypes as handed over [, the
        # probed While bounds]: the CompiledProgram that serves them}
        self.steps: Dict[Tuple, CompiledProgram] = {}


# Deferred bounded-While truncation flags are normally checked one run
# later (so the warn path never syncs the just-dispatched step); flush
# them at interpreter exit so a truncation on a session's FINAL run
# still warns without requiring Executor.close().
_LIVE_EXECUTORS: "weakref.WeakSet[Executor]" = weakref.WeakSet()


@atexit.register
def _flush_deferred_while_flags():
    for ex in list(_LIVE_EXECUTORS):
        flags, ex._deferred_flags = ex._deferred_flags, []
        for key, v in flags:
            _check_while_flag(key, v, raise_=False)


class Executor:
    """Runs Programs on JAX's default backend, which is process-global
    (``JAX_PLATFORMS``; a TPU when one is attached). `place` does not
    move the computation, but a place that names a device must find
    it: ``TPUPlace`` raises here when the default backend is not a TPU
    (paddle_tpu/executor.py), so ``Executor(TPUPlace())`` can never
    run on the CPU without a word."""

    def __init__(self, place=None, donate_state: Optional[bool] = None):
        self.place = place
        if hasattr(place, "require"):
            place.require()
        place_compile_cache()
        _listen_to_compiles()
        # donate_state=None reads PADDLE_TPU_DONATE_STATE (default on).
        self.donate_state = DONATE_STATE_DEFAULT if donate_state is None \
            else bool(donate_state)
        # state arrays written by the most recent run(): the sync
        # barrier set for synchronize() (checkpoint snapshots must not
        # race an in-flight async step)
        self._inflight_state: List[Any] = []
        self._cache: Dict[Tuple, CompiledProgram] = {}
        self._probe_cache: Dict[Tuple, Any] = {}
        # the bound steps: what run() resolved once for each program as
        # it is called (_Binding). Like _cache, read and filled from
        # several threads: one dict operation each, and two threads
        # that bind one key build equal records
        self._bindings: Dict[Tuple, _Binding] = {}
        # bounded-While truncation flags from the PREVIOUS run, checked
        # one step later so the warn-by-default path never forces a
        # device sync on the just-dispatched step
        self._deferred_flags: List[Tuple[Tuple, Any]] = []
        # compile-cache hit/miss counters: a hit means run() dispatched
        # an already-jitted executable; a miss means it traced+compiled.
        # Serving reads these for its compile_cache_hit_rate metric.
        self.cache_stats: Dict[str, int] = {"hits": 0, "misses": 0}
        # static ProgramCost of the most recently dispatched executable
        # — the numerator of the live MFU gauge (trainer, serving)
        self.last_cost = None
        # static MemoryReport of the same executable (analysis/memory):
        # peak-HBM estimate + liveness, attached next to last_cost
        self.last_memory = None
        _LIVE_EXECUTORS.add(self)

    # ------------------------------------------------------------------
    @staticmethod
    def compile_key(program, feed_sig, fetch_names, block_idx: int = 0,
                    while_bounds=None, iterations: int = 1,
                    stacked_feed: bool = False, amp=None,
                    donate=None) -> Tuple:
        """The compile-cache key for one (program, feed signature, fetch
        list) combination — the public form of the private cache tuple,
        so callers (serving warmup, cache probes) can reason about
        executable reuse without duplicating the key layout. `feed_sig`
        comes from `feed_signature`; `amp=None` reads the ambient AMP
        state, matching what run() would use; `donate=None` reads the
        process default (donation aliases state-in to state-out, a
        different executable than the copy-per-step build, so it is
        part of the key)."""
        if hasattr(program, "desc"):
            program = program.desc
        return (program.uid, program.version, feed_sig,
                tuple(fetch_names), block_idx,
                amp_enabled() if amp is None else bool(amp),
                tuple(sorted(while_bounds.items())) if while_bounds
                else None, iterations, stacked_feed,
                DONATE_STATE_DEFAULT if donate is None else bool(donate))

    # ------------------------------------------------------------------
    def _probe_while_bounds(self, program: Program, block: BlockDesc,
                            targets, prefix: int, feed_vals, feed_sig,
                            scope: Scope, block_idx: int, step):
        """Probe-and-replay WhileGrad, phase 1 (reference analog:
        while_op.cc:96 step scopes — there the forward RECORDS per-step
        state; here, XLA-native, the forward prefix RE-RUNS to measure
        each dynamic loop's trip count, and phase 2 recompiles the full
        program with the bucketed bound baked into a differentiable
        masked scan). State writes are discarded — the probe is pure.
        `targets` and `prefix` are `_dynamic_while_targets(block)`'s.
        Returns {while_id: bound}."""
        steps_names = list(targets.values())
        pkey = (program.uid, program.version, feed_sig, block_idx,
                "__probe__")
        probe = self._probe_cache.get(pkey)
        if probe is None:
            view = _BlockPrefix(block, prefix)
            read_names, _ = _collect_state_names(program, view, scope)

            def probe_fn(feed_vals, state, step):
                env = dict(state)
                env.update(feed_vals)
                extra = {
                    "program": program,
                    "step": step,
                    "keep_vars": set(steps_names),
                    "prng": lambda seed: jax.random.fold_in(
                        jax.random.PRNGKey(seed), step),
                }
                env = trace_block(view, env, extra)
                return [env[n] for n in steps_names]

            probe = (jax.jit(probe_fn), read_names)
            self._probe_cache[pkey] = probe
        jitted, read_names = probe
        state = {n: scope.get(n) for n in read_names}
        counts = jitted(feed_vals, state, step)
        return {wid: _next_pow2(int(np.asarray(c)))
                for wid, c in zip(targets, counts)}

    # ------------------------------------------------------------------
    def _compile(self, program: Program, block: BlockDesc,
                 feed_sig, fetch_names: Sequence[str],
                 scope: Scope,
                 while_bounds=None, iterations: int = 1,
                 or_reduce_tail: int = 0,
                 stacked_feed: bool = False,
                 donate: bool = True) -> CompiledProgram:
        read_names, write_names = _collect_state_names(program, block, scope)
        fetch_names = list(fetch_names)
        feed_names = [k for k, _ in feed_sig or ()]
        # Donate only buffers that are overwritten (param updates); read-only
        # state (e.g. params in a forward-only program) must survive the call.
        written = set(write_names)
        rw_names = sorted(n for n in read_names if n in written)
        ro_names = sorted(n for n in read_names if n not in written)

        def one_step(feed_vals: Dict[str, Any], ro_state: Dict[str, Any],
                     rw_state: Dict[str, Any], step: jnp.ndarray):
            env: Dict[str, Any] = {}
            env.update(ro_state)
            env.update(rw_state)
            env.update(feed_vals)
            extra = {
                "program": program,
                "step": step,
                "keep_vars": set(fetch_names) | set(write_names),
                "prng": lambda seed: jax.random.fold_in(
                    jax.random.PRNGKey(seed), step),
            }
            if while_bounds:
                extra["while_bounds"] = while_bounds
            env = trace_block(block, env, extra)
            fetches = [env[n] for n in fetch_names]
            new_state = {n: env[n] for n in write_names if n in env}
            return fetches, new_state

        # what is jitted takes its feeds and its state as sequences
        # (by_name) and returns the step counter advanced, so a call
        # builds no dict for the flattening to sort and launches
        # nothing beside the step
        if iterations == 1:
            def step_fn(feed_vals, ro_state, rw_state, step):
                fetches, new_state = one_step(
                    by_name(feed_names, feed_vals),
                    by_name(ro_names, ro_state),
                    by_name(rw_names, rw_state), step)
                with jax.named_scope(STEP_SCOPE):
                    return fetches, new_state, step + 1

            fn = step_fn
        else:
            n_flags = int(or_reduce_tail)

            def fn(feed_vals, ro_state, rw_state, step):
                feed_vals = by_name(feed_names, feed_vals)
                ro_state = by_name(ro_names, ro_state)
                rw_state = by_name(rw_names, rw_state)
                # K steps inside ONE compiled program (lax.scan over the
                # traced step): per-dispatch overhead is paid once per K
                # real steps, which is what makes ms-scale steps
                # measurable through a high-RTT link. With
                # stacked_feed, feed arrays carry a leading K axis and
                # the scan consumes one slice per iteration (K DISTINCT
                # batches — unchanged SGD semantics); otherwise every
                # iteration re-reads the same feed. rw state chains
                # through the scan carry. Fetches and write-only state
                # thread through the carry too (zero-init from
                # eval_shape) — stacking K histories just to slice [-1]
                # would cost K x device memory. The trailing `n_flags`
                # fetches are bounded-While truncation flags: those OR
                # across iterations, so a loop truncated at iteration 3
                # of 64 still trips the check.
                feed0 = {k: v[0] for k, v in feed_vals.items()} \
                    if stacked_feed else feed_vals
                zeros = jax.tree_util.tree_map(
                    lambda a: jnp.zeros(a.shape, a.dtype),
                    jax.eval_shape(
                        lambda rw, st: one_step(feed0, ro_state,
                                                rw, st),
                        rw_state, step))
                f0, ns0 = zeros
                e0 = {n: v for n, v in ns0.items() if n not in rw_names}
                first_flag = len(fetch_names) - n_flags

                def body(carry, xs):
                    rw_c, st, f_c, _e_c = carry
                    step_feed = xs if stacked_feed else feed_vals
                    fetches, new_state = one_step(step_feed, ro_state,
                                                  rw_c, st)
                    rw_next = {n: new_state.get(n, rw_c[n])
                               for n in rw_names}
                    # e0 keys come from the eval_shape trace of this
                    # very one_step, so every one must be produced here
                    # too — index directly so a divergence fails loudly
                    # instead of silently writing the zero placeholder
                    # back to the scope
                    extra_w = {n: new_state[n] for n in e0}
                    f_out = [
                        jnp.logical_or(f_c[i], f) if i >= first_flag
                        else f
                        for i, f in enumerate(fetches)]
                    with jax.named_scope(STEP_SCOPE):
                        st = st + 1
                    return (rw_next, st, f_out, extra_w), None

                (rw_f, step, fetches, extra_w), _ = jax.lax.scan(
                    body, (rw_state, step, f0, e0),
                    xs=feed_vals if stacked_feed else None,
                    length=iterations)
                new_state = dict(rw_f)
                new_state.update(extra_w)
                return fetches, new_state, step

        # donate=True aliases the rw state (argnum 2) in XLA: state-out
        # writes land in the state-in buffers instead of fresh
        # allocations, removing the per-step state-copy traffic. The
        # caller-side contract — the scope-held input arrays are DEAD
        # after the call — is enforced in run() (scope is repointed at
        # the outputs, and stragglers are erased).
        jitted = jax.jit(fn, donate_argnums=(2,) if donate else ())
        return CompiledProgram(jitted, read_names, write_names,
                               fetch_names, jitted=jitted,
                               ro_names=ro_names, rw_names=rw_names,
                               block=block, feed_names=feed_names)

    # ------------------------------------------------------------------
    def run(self, program: Program, feed: Optional[Dict[str, Any]] = None,
            fetch_list: Optional[Sequence] = None, scope: Optional[Scope] = None,
            return_numpy: bool = True, block_idx: int = 0,
            iterations: int = 1, stacked_feed: bool = False,
            sync: bool = True):
        """Execute `program` block `block_idx` with `feed`, return fetches.

        feed values: numpy arrays, python scalars, or LoDTensor for ragged.
        fetch_list entries: var names or objects with a `.name`.

        sync=False returns a `StepResult` instead of materialized
        fetches: the step is dispatched (and persistable state in the
        scope already points at the new device arrays), but
        device->host transfer happens only when a fetched value is
        consumed, so the host can feed/dispatch the NEXT step while
        this one computes. With state donation on, fetching an rw
        (donated) state var asynchronously is rejected — the lazy
        handle would alias a buffer the next step donates.

        iterations > 1 runs the block that many times inside ONE compiled
        program (a lax.scan over the traced step, state chained through
        the carry): the analog of the reference's repeated Executor.Run
        over a prepared context (executor.cc RunPreparedContext), but
        paying per-call dispatch once per K steps. With
        stacked_feed=True each feed array carries a leading axis of
        length `iterations` and every scan iteration consumes its own
        slice — K DISTINCT batches per dispatch, unchanged SGD
        semantics. Without it, every iteration re-reads the same feed
        (useful for perf probes only). Fetches are the FINAL
        iteration's values; the step counter advances by `iterations`.
        Rejected for programs with host-side stateful ops
        (channels/select/go — host callbacks under scan are unverified)
        or unbounded-While gradients (the trip count is probed against
        the INITIAL state only).
        """
        if hasattr(program, "desc"):  # accept the python builder wrapper
            program = program.desc
        scope = global_scope() if scope is None else scope
        with profiler.RecordEvent("pipeline::prepare",
                                  cat=profiler.CAT_PIPELINE) as span:
            binding, compiled, missed, outcome, args = self._prepare(
                program, feed or {}, fetch_list, scope, block_idx,
                iterations, stacked_feed, sync)
            span.args = {"bound": outcome}
        # a first dispatch traces, lowers and compiles inside the
        # jitted call: JAX's compile-phase events fired there carry
        # this program's uid (_on_jax_duration)
        _compiling.args = {"uid": program.uid, "block": block_idx} \
            if missed else None
        try:
            with profiler.RecordEvent("pipeline::dispatch",
                                      cat=profiler.CAT_PIPELINE):
                fetches, new_state, step = compiled.fn(*args)
        finally:
            _compiling.args = None
        with profiler.RecordEvent("pipeline::commit",
                                  cat=profiler.CAT_PIPELINE):
            result = self._commit(program, scope, binding, compiled,
                                  fetches, new_state, step, return_numpy)
        return result.fetches() if sync else result

    def _prepare(self, program, feed, fetch_list, scope, block_idx,
                 iterations, stacked_feed, sync):
        """run()'s host work before the dispatch. A call a record
        serves (every call after the first of its shape) looks the
        record up from what it can observe — the program's uid and
        version, how it is called, the feeds' names, shapes and dtypes
        — and reads the state the record names from the scope: work in
        the feeds and the state names, none in the program's ops.
        Returns (the _Binding, the CompiledProgram, whether it was
        compiled now, "hit" or "bound", the arguments of its `fn`)."""
        fetch_names = [f if isinstance(f, str) else f.name
                       for f in (fetch_list or [])]
        from ..analysis import verifier as _verifier
        verify = _verifier.verify_enabled()
        bkey = (program.uid, program.version, block_idx,
                tuple(fetch_names), iterations, stacked_feed, sync,
                self.donate_state, amp_enabled(), verify)
        block = program.block(block_idx)
        binding = self._bindings.get(bkey)
        if binding is None:
            binding = self._bindings[bkey] = _Binding(block, fetch_names)

        # Pre-compile safety gate: structural verification (def-use,
        # build-time shape markers, dead code, donation hazards) BEFORE
        # any trace or XLA compile, so a malformed program raises a
        # VerificationError (a ValueError) naming the op and block path
        # instead of a deep JAX trace error. Passed once per binding
        # and set of feeds; PADDLE_TPU_VERIFY=0 opts out.
        feed_names = tuple(feed)
        if verify and feed_names not in binding.gated:
            _verifier.executor_gate(program, block_idx, fetch_names,
                                    feed_names, self.donate_state, sync)
            binding.gated.add(feed_names)

        feed_vals, sig = {}, []
        for k, v in feed.items():
            feed_vals[k], abstract = _feed_arg(v)
            sig.append((k, abstract))
        step = scope.find(STEP_VAR)
        if step is None:
            step = jnp.zeros((), jnp.int32)

        # validate stacked feeds BEFORE the While probe: probing with
        # (K, batch, ...) shapes the program was never built for would
        # die in an opaque trace error instead of the messages below
        if stacked_feed:
            if iterations == 1:
                raise ValueError("stacked_feed requires iterations > 1")
            for k_, v_ in feed_vals.items():
                if not hasattr(v_, "shape"):
                    raise ValueError(
                        f"stacked_feed: feed {k_!r} is not an array "
                        "(ragged/LoDTensor feeds cannot be stacked — "
                        "their padded length may differ per batch)")
                if v_.shape[:1] != (iterations,):
                    raise ValueError(
                        f"stacked_feed: feed {k_!r} leading dim "
                        f"{v_.shape[:1]} != iterations {iterations}")

        # unbounded-While gradients: measure trip counts with a forward
        # probe, then compile with the bucketed bounds baked in; with
        # stacked feeds the probe sees one PER-STEP slice. The counts
        # depend on the feed's VALUES, so such a program probes on
        # every call; any other never enters the probe.
        probe = binding.probe
        if probe is None:
            probe = binding.probe = self._probe_plan(program, block)
        skey = tuple(sig)
        while_bounds = None
        if probe:
            probe_feed = {k_: v_[0] for k_, v_ in feed_vals.items()} \
                if stacked_feed else feed_vals
            while_bounds = self._probe_while_bounds(
                program, block, *probe, probe_feed, tuple(sorted(sig)),
                scope, block_idx, step)
            skey += tuple(sorted(while_bounds.items()))

        _, obs_hits, _, obs_donate, obs_bound = _obs_instruments()
        obs_donate.set(1.0 if self.donate_state else 0.0)
        compiled = binding.steps.get(skey)
        missed = False
        if compiled is None:
            outcome = "bound"
            compiled, missed = self._bind_step(
                program, block, binding, feed_vals, tuple(sorted(sig)),
                while_bounds, scope, block_idx, iterations, stacked_feed,
                sync)
            binding.steps[skey] = compiled
        else:
            outcome = "hit"
            self.cache_stats["hits"] += 1
            obs_hits.inc()
        obs_bound[outcome].inc()
        self.last_cost = compiled.cost
        self.last_memory = compiled.memory

        args = ([feed_vals[k] for k in compiled.feed_names],
                scope.values_of(compiled.ro_names),
                scope.values_of(compiled.rw_names), step)
        if missed:
            compiled.record_avals(*args)
        # the arrays of the last feed, for a caller that traces the
        # jitted stage itself (AOT lowering reads compiled.avals)
        self._last_feed_vals = feed_vals
        return binding, compiled, missed, outcome, args

    @staticmethod
    def _probe_plan(program: Program, block: BlockDesc) -> Tuple:
        """`_dynamic_while_targets(block)` where a probe has something
        to measure, () where the block has no such While."""
        targets, prefix = _dynamic_while_targets(block)
        if not targets:
            return ()
        stateful = _stateful_ops_in(program, block.ops[:prefix])
        if stateful:
            raise RuntimeError(
                "cannot differentiate an unbounded While in a program "
                f"whose forward prefix has stateful ops {sorted(set(stateful))}: "
                "the trip-count probe re-executes that prefix, which "
                "would fire each channel/select/go op twice per step. "
                "Give the While an explicit max_steps, or move the CSP "
                "ops after the last dynamic While.")
        return targets, prefix

    def _bind_step(self, program, block, binding, feed_vals, feed_sig,
                   while_bounds, scope, block_idx, iterations,
                   stacked_feed, sync):
        """The CompiledProgram for `binding` under this feed signature
        (and these While bounds): the compile cache's where it holds
        one, compiled now where not. Returns (it, whether it was
        compiled now)."""
        fetch_names = binding.fetch_names
        n_user_fetches = binding.n_user_fetches
        if iterations < 1:
            raise ValueError(
                f"iterations must be >= 1, got {iterations}: a "
                "zero-length scan would return zero-initialized "
                "fetches without running anything")
        if iterations > 1:
            if while_bounds:
                raise RuntimeError(
                    "iterations > 1 is incompatible with unbounded-While "
                    "gradients: the trip-count probe measures the initial "
                    "state only, but later scan iterations may need a "
                    "larger bound. Run steps one at a time.")
            stateful = binding.stateful
            if stateful is None:
                stateful = binding.stateful = \
                    _stateful_ops_in(program, block.ops)
            if stateful:
                raise RuntimeError(
                    f"iterations > 1 with stateful ops "
                    f"{sorted(set(stateful))}: host-side channel/select/go "
                    "callbacks inside a compiled scan are unverified. Run "
                    "steps one at a time.")

        key = self.compile_key(program, feed_sig, fetch_names, block_idx,
                               while_bounds=while_bounds,
                               iterations=iterations,
                               stacked_feed=stacked_feed,
                               donate=self.donate_state)
        _, obs_hits, obs_misses = _obs_instruments()[:3]
        compiled = self._cache.get(key)
        missed = compiled is None
        if missed:
            self.cache_stats["misses"] += 1
            obs_misses.inc()
            span_args = {"uid": program.uid, "block": block_idx}
            kw = {} if iterations == 1 else {
                "iterations": iterations,
                "or_reduce_tail": len(fetch_names) - n_user_fetches,
                "stacked_feed": stacked_feed}
            # feed shapes of THIS dispatch, for the -1-dim binding of
            # the memory plan and the cost model below (stacked feeds
            # strip the leading K axis — both analyses are per traced
            # iteration)
            fs = {}
            for fk, fv in feed_vals.items():
                shp = getattr(fv, "shape", None)
                if isinstance(shp, tuple):
                    fs[fk] = shp[1:] if stacked_feed else shp
            # Pre-compile OOM gate (analysis/memory.py): the static
            # peak-HBM plan of the program about to be compiled, its
            # free-at-last-use peak against the budget. An over-budget
            # program (PADDLE_TPU_HBM_BYTES, 0 disables) raises a
            # structured VerificationError naming the top offenders
            # and the high-water op BEFORE XLA ever sees it, instead
            # of an unattributed allocator failure deep inside
            # compilation. The plan itself is best-effort; the budget
            # check respects the PADDLE_TPU_VERIFY kill switch.
            from ..analysis import verifier as _verifier
            mem_report = None
            try:
                from ..analysis import memory as _memory
                with _compile_span("memory_plan", span_args):
                    mem_report = _memory.program_memory(
                        program, block_idx, feed_shapes=fs,
                        feed_names=feed_vals.keys(),
                        label=f"program uid={program.uid} "
                              f"block={block_idx}")
            except Exception:
                mem_report = None
            if mem_report is not None and _verifier.verify_enabled():
                _memory.check_budget(mem_report).raise_if_errors(
                    context="pre-compile memory gate")
            compiled = self._compile(program, block, feed_sig,
                                     fetch_names, scope,
                                     while_bounds=while_bounds,
                                     donate=self.donate_state, **kw)
            compiled.memory = mem_report
            # static cost attribution, attached once per compiled
            # executable: per-op FLOPs/bytes with the dynamic batch dim
            # bound from THIS dispatch's feed shapes. Best-effort: the
            # cost model must never fail a compile.
            try:
                from ..analysis import cost_model as _cost_model
                with _compile_span("cost_model", span_args):
                    compiled.cost = _cost_model.program_cost(
                        program, block_idx, feed_shapes=fs)
            except Exception:
                compiled.cost = None
            self._cache[key] = compiled
            with _compiled_lock:
                _compiled_programs.append(compiled)
        else:
            self.cache_stats["hits"] += 1
            obs_hits.inc()

        if not sync and self.donate_state:
            rw = set(compiled.rw_names)
            aliased = [n for n in fetch_names[:n_user_fetches] if n in rw]
            if aliased:
                raise ValueError(
                    f"sync=False cannot fetch donated state vars "
                    f"{aliased}: the lazy StepResult would hold a buffer "
                    "the next step donates (and XLA deletes). Fetch them "
                    "with sync=True, or build the Executor with "
                    "donate_state=False.")
        return compiled, missed

    def _commit(self, program, scope, binding, compiled, fetches,
                new_state, step, return_numpy):
        """run()'s host work after the dispatch: the scope repointed at
        the new state and at the step counter the compiled step
        advanced, the While flags, the StepResult."""
        scope.set(STEP_VAR, step)
        scope.update(new_state)
        if self.donate_state:
            # every donated input buffer is dead after the call; the
            # update above repointed scope at the outputs for vars the
            # trace produced — explicitly drop any donated name the
            # trace did NOT write back, so a later scope read fails
            # loudly (KeyError) instead of returning a deleted buffer
            unwritten = compiled.unwritten
            if unwritten is None:
                unwritten = compiled.unwritten = [
                    n for n in compiled.rw_names if n not in new_state]
            for n in unwritten:
                scope.erase(n)
        self._inflight_state = new_state

        n_user_fetches = binding.n_user_fetches
        fetch_names = binding.fetch_names
        flag_vals = list(zip(fetch_names[n_user_fetches:],
                             fetches[n_user_fetches:]))
        if CHECK_WHILE_BOUND:
            # enforced mode reads the flags synchronously so the raise
            # points at the offending step
            for n, v in flag_vals:
                _check_while_flag((program.uid, n), v, raise_=True)
        elif flag_vals or self._deferred_flags:
            # warn mode: consume deferred flags whose arrays are
            # already resident — reading those is free — and KEEP
            # deferring any still in flight, so back-to-back async
            # dispatches are never capped by the check (a pipelined
            # loop drains them one-to-two steps late; close()/atexit
            # flushes stragglers with a sync)
            still = []
            for fkey, v in self._deferred_flags:
                if getattr(v, "is_ready", lambda: True)():
                    _check_while_flag(fkey, v, raise_=False)
                else:
                    still.append((fkey, v))
            still.extend(((program.uid, n), v) for n, v in flag_vals)
            self._deferred_flags = still
        result = StepResult(fetches[:n_user_fetches],
                            fetch_names[:n_user_fetches], return_numpy,
                            nan_check=CHECK_NAN_INF,
                            trace_ctx=obs_trace.current())
        # THIS dispatch's static cost rides on the result: consumers on
        # other threads (serving workers sharing one executor) must not
        # read the executor-global last_cost, which the next dispatch
        # overwrites
        result.cost = compiled.cost
        result.memory = compiled.memory
        return result

    def cost_for(self, program):
        """The static ProgramCost attached to a compiled executable of
        ``program`` (any feed signature), or None if none was compiled
        by this executor yet."""
        desc = program.desc if hasattr(program, "desc") else program
        # snapshot: a concurrent run() populating the cache on a miss
        # must not blow up this introspection with a resize error
        for k, compiled in list(self._cache.items()):
            # (uid, version) — a superseded build of the same program
            # may still sit in the cache; its cost describes a graph
            # that no longer exists
            if k[0] == desc.uid and k[1] == desc.version \
                    and compiled.cost is not None:
                return compiled.cost
        return None

    def cost_table(self, program=None, limit: int = 20,
                   measured=None) -> Optional[str]:
        """Rendered per-op cost table for ``program`` (default: the
        most recently dispatched executable) — the Executor-level view
        of the always-on attribution. Handed ``measured``, one device's
        entry of `profiler.device_op_times`, the rows are the ops the
        device spent time on, heaviest first, each static count beside
        the seconds measured, the share of busy time and the FLOP/s and
        GB/s they make (`profiler.op_time_table`)."""
        if measured is not None:
            desc = getattr(program, "desc", program)
            return profiler.op_time_table(
                measured, by="op", limit=limit,
                uid=None if desc is None else desc.uid)
        cost = self.cost_for(program) if program is not None \
            else self.last_cost
        return None if cost is None else cost.table(limit=limit)

    def synchronize(self):
        """Barrier: block until every state write dispatched by this
        executor is resident on device. Checkpoint saves during async
        training call this before snapshotting persistable state, so a
        snapshot can never race the in-flight step (and an async XLA
        error surfaces here, at a named point, instead of inside the
        tmp-write)."""
        # distinct from pipeline::host_blocked (feed-phase time): this
        # wait is DEVICE time, and the attribution breakdown charges
        # unmapped events to the device residual
        with profiler.RecordEvent("pipeline::sync_barrier",
                                  cat=profiler.CAT_PIPELINE):
            for leaf in jax.tree_util.tree_leaves(self._inflight_state):
                if hasattr(leaf, "block_until_ready"):
                    leaf.block_until_ready()
            self._inflight_state = []
        return self

    def close(self):
        for key, v in self._deferred_flags:
            _check_while_flag(key, v, raise_=False)
        self._deferred_flags = []
        self._inflight_state = []
        self._cache.clear()
        self._probe_cache.clear()
        self._bindings.clear()
