"""Streaming input plane: a sharded multi-process input service.

The TensorFlow-paper input-pipeline story (PAPERS.md) rebuilt on this
repo's reader/resilience/observability stack: recordio shards are
divided across N worker PROCESSES (decode + block shuffle off the
trainer host path), finished fixed-shape batches stream back through
shared-memory ring slots (the `multiprocess.py` transport), and the
consumer performs an exact deterministic merge so the delivered stream
is **bit-identical to a single-process reader** — across worker counts,
elastic rescales, worker crashes, and mid-epoch checkpoint/restore.

Determinism contract
--------------------
Every shard yields a deterministic batch stream: records are read
sequentially in blocks of ``shuffle_block_batches * batch_size``
records, each block is shuffled with a seed derived from
``(seed, shard, epoch, block)``, and consecutive ``batch_size`` groups
become batches (the trailing partial batch of a shard-epoch is
dropped — fixed shapes only). The global stream is the k-way merge of
all shard streams ordered by ``(epoch, batch_no, shard)``. Workers
produce their shards' batches in exactly that order restricted to their
shards, and the consumer delivers in the full order — so
``iter_stream(cfg)`` (single process, no workers) and
``StreamingInputService(cfg).reader()`` yield identical sequences.

That ordering is also the liveness argument: a worker's
produced-but-undelivered slots are always the globally-next batches of
its own shards, so the consumer can always deliver the earliest of them
and hand the slot back — bounded memory (``slots_per_worker`` per
worker), no deadlock.

Cursors and resume
------------------
The delivery state is one pointer per shard — ``(epoch, next_batch)``
— plus the learned per-shard batch totals. ``state_for(k)`` returns the
state after ``k`` delivered batches (the Trainer checkpoints it beside
the weights via ``CheckpointConfig``; the FeedPrefetcher may have
pulled further ahead — snapshots are kept per delivery so the
checkpoint records the *trained* position). ``restore(state)`` seeds a
fresh service (or the single-process ``iter_stream``) to continue the
stream with no replayed and no skipped record.

Elasticity and resilience
-------------------------
The pool scales from live delivery stats: a window where more than
``scale_up_starved`` of deliveries found the queue dry spawns a worker;
a window with zero starvation and a full queue retires one. A rescale
is a pool restart from the delivered cursor (shards are repartitioned),
invisible in the delivered stream. A worker that dies — crash, OOM,
injected ``reader.shard`` fault — is detected, its ring is salvaged,
and it is respawned from the delivered cursor (at most ``max_respawns``
times service-wide); batches already in flight are deduplicated, so the
stream stays exact.
"""
from __future__ import annotations

import itertools
import multiprocessing as mp
import os
import queue as _queue
import threading
import time
import traceback
import zlib
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from multiprocessing import connection as mp_connection

from .multiprocess import (_EscapedSegment, ensure_resource_tracker,
                           new_shm_segment)

__all__ = ["StreamingConfig", "StreamingInputService", "iter_stream",
           "RawDecoder"]


class RawDecoder:
    """Picklable fixed-layout record decoder: splits each record into
    consecutive fixed-shape fields (e.g. ``[((1,), "int64"),
    ((3, 224, 224), "uint8")]`` for an 8-byte label followed by a raw
    CHW image). Works under the "spawn" start method — instances pickle
    by value, so no module-level decode function is needed."""

    def __init__(self, fields):
        self.fields = [(tuple(s), np.dtype(d)) for s, d in fields]
        self.record_bytes = sum(
            int(np.prod(s, dtype=np.int64)) * d.itemsize
            for s, d in self.fields)

    def __call__(self, rec: bytes):
        if len(rec) != self.record_bytes:
            raise ValueError(
                f"record is {len(rec)} bytes but this decoder's layout "
                f"needs exactly {self.record_bytes}")
        out, off = [], 0
        for shape, dt in self.fields:
            n = int(np.prod(shape, dtype=np.int64))
            out.append(np.frombuffer(rec, dt, count=n,
                                     offset=off).reshape(shape))
            off += n * dt.itemsize
        return tuple(out)


def _env(name: str, default):
    """Registered-flag read coerced to the default's type (every name
    passed here is in flags.FLAGS; flags.get is the shared resolver)."""
    from .. import flags
    return type(default)(flags.get(name))


class StreamingConfig:
    """Picklable configuration shared by the service, its worker
    processes, and the single-process reference stream.

    decode:  module-level callable ``record_bytes -> sample`` (a tuple
             of fixed-shape ndarrays, or one ndarray). Must be
             picklable by reference under the "spawn" start method.
    collate: optional ``list-of-samples -> tuple-of-batched-ndarrays``;
             default stacks each field.
    feed_names: when set, delivered batches are feed DICTS
             ``{name: array}`` (the Trainer path); otherwise tuples.
    shuffle_block_batches: records are shuffled within blocks of this
             many batches (0 = sequential). Blocks are the resume
             granularity: restoring mid-block re-reads the block and
             skips already-delivered batches.
    """

    def __init__(self, shards: Sequence[str], batch_size: int,
                 decode: Callable, collate: Optional[Callable] = None,
                 feed_names: Optional[Sequence[str]] = None,
                 epochs: int = 1, seed: int = 0,
                 shuffle_block_batches: int = 0,
                 workers: Optional[int] = None,
                 min_workers: Optional[int] = None,
                 max_workers: Optional[int] = None,
                 slots_per_worker: Optional[int] = None,
                 method: Optional[str] = None,
                 scale_interval_s: Optional[float] = None,
                 scale_up_starved: Optional[float] = None,
                 max_respawns: Optional[int] = None,
                 respawn_delay_s: float = 0.05):
        if not shards:
            raise ValueError("StreamingConfig needs at least one shard")
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        self.shards = [str(p) for p in shards]
        self.batch_size = int(batch_size)
        self.decode = decode
        self.collate = collate
        self.feed_names = tuple(feed_names) if feed_names else None
        self.epochs = int(epochs)
        self.seed = int(seed)
        self.shuffle_block_batches = int(shuffle_block_batches)
        self.workers = int(workers if workers is not None
                           else _env("PADDLE_TPU_INPUT_WORKERS", 2))
        self.min_workers = int(min_workers if min_workers is not None
                               else _env("PADDLE_TPU_INPUT_MIN_WORKERS", 1))
        self.max_workers = int(max_workers if max_workers is not None
                               else _env("PADDLE_TPU_INPUT_MAX_WORKERS", 4))
        self.slots_per_worker = int(
            slots_per_worker if slots_per_worker is not None
            else _env("PADDLE_TPU_INPUT_SLOTS", 4))
        self.method = str(method if method is not None
                          else _env("PADDLE_TPU_INPUT_START_METHOD",
                                    "spawn"))
        self.scale_interval_s = float(
            scale_interval_s if scale_interval_s is not None
            else _env("PADDLE_TPU_INPUT_SCALE_INTERVAL_S", 2.0))
        self.scale_up_starved = float(
            scale_up_starved if scale_up_starved is not None
            else _env("PADDLE_TPU_INPUT_SCALE_UP_STARVED", 0.25))
        self.max_respawns = int(max_respawns if max_respawns is not None
                                else _env("PADDLE_TPU_INPUT_MAX_RESPAWNS",
                                          3))
        self.respawn_delay_s = float(respawn_delay_s)
        if self.min_workers < 1 or self.max_workers < self.min_workers:
            raise ValueError(
                f"need 1 <= min_workers <= max_workers, got "
                f"{self.min_workers}..{self.max_workers}")
        if self.slots_per_worker < 2:
            # one slot being written while one is undelivered is the
            # minimum for any overlap at all
            raise ValueError("slots_per_worker must be >= 2")


# -- deterministic per-shard stream (shared by workers and reference) -------

def _block_rng(seed: int, shard: int, epoch: int, block: int):
    h = zlib.crc32(f"{seed}:{shard}:{epoch}:{block}".encode())
    return np.random.RandomState(h & 0x7FFFFFFF)


def _default_collate(samples):
    first = samples[0]
    if not isinstance(first, tuple):
        return (np.stack(samples),)
    return tuple(np.stack([s[i] for s in samples])
                 for i in range(len(first)))


def _shard_stream(cfg: StreamingConfig, shard: int,
                  start_epoch: int = 0, start_batch: int = 0):
    """Deterministic batch stream of one shard: yields
    ``("batch", epoch, batch_no, arrays)`` in order, and
    ``("eof", epoch, total_batches)`` after each epoch's last batch.
    Resumable at any ``(epoch, batch)``: fully-consumed shuffle blocks
    are skipped without decoding; a partially-delivered block is
    re-read and its delivered batches skipped."""
    from .. import recordio
    from ..resilience import faults

    bs = cfg.batch_size
    bb = max(1, cfg.shuffle_block_batches)
    block_recs = bb * bs
    path = cfg.shards[shard]
    for epoch in range(start_epoch, cfg.epochs):
        sb = start_batch if epoch == start_epoch else 0
        skip_blocks = sb // bb
        bno = skip_blocks * bb
        block_no = skip_blocks
        with recordio.Scanner(path) as sc:
            if skip_blocks:
                sc.skip(skip_blocks * block_recs)
            it = iter(sc)
            while True:
                recs = list(itertools.islice(it, block_recs))
                if not recs:
                    break
                if cfg.shuffle_block_batches > 0:
                    order = _block_rng(cfg.seed, shard, epoch,
                                       block_no).permutation(len(recs))
                    recs = [recs[i] for i in order]
                for j in range(len(recs) // bs):
                    if bno < sb:
                        bno += 1
                        continue
                    samples = [cfg.decode(r)
                               for r in recs[j * bs:(j + 1) * bs]]
                    arrays = (cfg.collate(samples) if cfg.collate
                              else _default_collate(samples))
                    faults.fire("reader.shard")
                    yield ("batch", epoch, bno, arrays)
                    bno += 1
                block_no += 1
                if len(recs) < block_recs:
                    break  # final partial block: trailing partial batch dropped
        yield ("eof", epoch, bno)


def _merged(cfg: StreamingConfig, starts: Dict[int, Tuple[int, int]]):
    """k-way merge of the given shards' streams by (epoch, batch, shard)
    — THE global delivery order. ``starts`` maps shard -> (epoch,
    batch); shards past cfg.epochs are omitted by the caller."""
    gens, pending = {}, {}
    for s, (e0, b0) in starts.items():
        if e0 >= cfg.epochs:
            continue
        g = _shard_stream(cfg, s, e0, b0)
        item = next(g, None)
        if item is not None:
            gens[s], pending[s] = g, item
    while pending:
        s = min(pending, key=lambda t: (pending[t][1], pending[t][2], t))
        yield s, pending[s]
        nxt = next(gens[s], None)
        if nxt is None:
            del gens[s], pending[s]
        else:
            pending[s] = nxt


def _as_feed(cfg: StreamingConfig, arrays):
    if cfg.feed_names is not None:
        if len(cfg.feed_names) != len(arrays):
            raise ValueError(
                f"decode produced {len(arrays)} fields but feed_names "
                f"has {len(cfg.feed_names)} entries")
        return dict(zip(cfg.feed_names, arrays))
    return arrays


def _starts_from_state(cfg: StreamingConfig,
                       state: Optional[dict]) -> Dict[int, Tuple[int, int]]:
    starts = {s: (0, 0) for s in range(len(cfg.shards))}
    if state:
        _check_state(cfg, state)
        for s_str, (e, b) in state["shards"].items():
            starts[int(s_str)] = (int(e), int(b))
    return starts


def _check_state(cfg: StreamingConfig, state: dict):
    want = {"nshards": len(cfg.shards), "batch_size": cfg.batch_size,
            "seed": cfg.seed,
            "shuffle_block_batches": cfg.shuffle_block_batches,
            "epochs": cfg.epochs}
    got = state.get("config", {})
    for k, v in want.items():
        if got.get(k) != v:
            raise ValueError(
                f"input-state mismatch: checkpoint has {k}={got.get(k)!r}"
                f" but this config has {v!r} — the cursor is only valid "
                "for the stream parameters it was taken under")


def iter_stream(cfg: StreamingConfig, state: Optional[dict] = None):
    """Single-process reference stream: yields EXACTLY the batches, in
    exactly the order, the multi-process service delivers — the
    bit-identity baseline and the no-worker fallback."""
    for _s, item in _merged(cfg, _starts_from_state(cfg, state)):
        if item[0] == "batch":
            yield _as_feed(cfg, item[3])


# -- worker process ---------------------------------------------------------

def _service_worker_main(wid, specs, cfg, slots, free_q, out_q, stop_ev,
                         consumer_pid):
    """One worker: produce the merged stream of its shards (delivery
    order restricted to them) into a shared-memory ring. specs:
    [(shard, start_epoch, start_batch)].

    Each worker OWNS its result queue: a worker SIGKILLed mid-put can
    wedge only its own queue's write lock, never the siblings' — the
    consumer simply stops reading a retired incarnation's queue.

    numpy only (recordio scan, decode, collate, shm copy): the chip
    belongs to the consumer process, and a worker that touched jax on
    a machine with a chip would hang or die on the TPU library's lock
    — cfg.decode / cfg.collate must keep it so (chip_smoke.py's
    streaming-fed steps are the standing evidence)."""
    shms: List = []
    layout = None
    try:
        starts = {s: (e0, b0) for s, e0, b0 in specs}
        for s, item in _merged(cfg, starts):
            if stop_ev.is_set():
                return
            if item[0] == "eof":
                out_q.put(("eof", wid, s, item[1], item[2]))
                continue
            _, epoch, bno, batch = item
            arrays = tuple(np.ascontiguousarray(a) for a in batch)
            lay = [(a.shape, str(a.dtype)) for a in arrays]
            if layout is None:
                layout = lay
                total = sum(a.nbytes for a in arrays)
                shms = [new_shm_segment(total, consumer_pid)
                        for _ in range(slots)]
                out_q.put(("meta", wid,
                            [m.name for m in shms], layout))
                for i in range(slots):
                    free_q.put(i)
            elif lay != layout:
                raise ValueError(
                    f"shard {s} produced batch layout {lay} but this "
                    f"service's ring is sized for {layout}: all shards "
                    "of one service must share a fixed batch schema")
            while True:
                try:
                    slot = free_q.get(timeout=0.2)
                    break
                except _queue.Empty:
                    if stop_ev.is_set():
                        return
            buf = shms[slot].buf
            off, dst = 0, None
            for a in arrays:
                dst = np.frombuffer(buf, dtype=a.dtype, count=a.size,
                                    offset=off).reshape(a.shape)
                np.copyto(dst, a)
                off += a.nbytes
            del dst, buf  # live exports block shm.close() later
            out_q.put(("batch", wid, s, epoch, bno, slot))
    except BaseException:  # noqa: BLE001 — surfaced via respawn/raise
        try:
            out_q.put(("error", wid, traceback.format_exc()[-4000:]))
        except BaseException:
            pass
    finally:
        try:
            # hold the ring until every slot id is back (the consumer
            # releases each slot as it delivers its batch)
            returned = 0
            while shms and returned < slots and not stop_ev.is_set():
                try:
                    free_q.get(timeout=0.2)
                    returned += 1
                except _queue.Empty:
                    if stop_ev.is_set():
                        break
            for m in shms:
                try:
                    m.close()
                except BufferError:
                    pass
                try:
                    m.unlink()
                except FileNotFoundError:
                    pass
        except BaseException:
            pass
        try:
            out_q.put(("done", wid))
        except BaseException:
            pass


# -- the service ------------------------------------------------------------

class StreamingInputService:
    """Sharded multi-process input service (module docstring has the
    full story). Single consumer: one `reader()` iteration at a time.
    Lifecycle: lazily starts its worker pool on first `reader()` pull;
    `stop()` (or the context manager) tears it down; `restore(state)`
    must run before the pool starts."""

    #: Trainer.train duck-types on this to route reader= through the
    #: service path (cursor checkpointing, live input metrics).
    is_streaming_input_service = True

    def __init__(self, config: Optional[StreamingConfig] = None, **kw):
        self.cfg = config if config is not None else StreamingConfig(**kw)
        n = len(self.cfg.shards)
        self._e = {s: 0 for s in range(n)}      # per-shard epoch pointer
        self._b = {s: 0 for s in range(n)}      # per-shard next batch
        self._fin: set = set()                  # shards past cfg.epochs
        self._totals: Dict[int, int] = {}       # learned batches/epoch
        self._delivered = 0
        # cursor reconstruction: per delivery we log only the CHANGED
        # shard pointer (delivered_no, shard, prev_epoch, prev_batch) —
        # state_for(k) rebuilds the k-delivery state by walking the
        # tail of this log backwards from the live pointers, so the
        # hot path never materializes a full O(n_shards) snapshot
        self._snap_log: deque = deque(maxlen=4096)
        self._snap_lock = threading.Lock()
        self._started = False
        self._stopped = False
        self._fatal: Optional[BaseException] = None
        self._respawns = 0
        self._scale_events = {"up": 0, "down": 0}
        self._next_wid = 0
        self._workers: Dict[int, dict] = {}
        self._rings: Dict[int, tuple] = {}      # wid -> (shms, views, label)
        self._buffer: Dict[tuple, tuple] = {}   # (e,b,s) -> entry
        self._ctx = None
        self._stop_ev = None
        self._last_liveness = 0.0
        # elastic-scaling window
        self._win_t0 = time.monotonic()
        self._win_deliv = 0
        self._win_starved = 0
        self._win_min_occ = None
        self._metrics = None

    # -- lifecycle -----------------------------------------------------
    def start(self) -> "StreamingInputService":
        if self._stopped:
            raise RuntimeError("service already stopped")
        if self._started:
            return self
        ensure_resource_tracker()
        self._ctx = mp.get_context(self.cfg.method)
        self._stop_ev = self._ctx.Event()
        self._init_metrics()
        self._spawn_pool(self.cfg.workers)
        self._started = True
        return self

    def stop(self, timeout: float = 5.0):
        """Stop workers, reclaim rings, unlink shared memory. Idempotent."""
        if not self._started or self._stopped:
            self._stopped = True
            return
        self._stop_ev.set()
        for w in self._workers.values():
            w["proc"].join(timeout)
        for w in self._workers.values():
            if w["proc"].is_alive():
                w["proc"].terminate()
                w["proc"].join(1.0)
        # pull whatever made it into the queues so stale metas get
        # attached and unlinked rather than leaked
        for w in list(self._workers.values()):
            self._drain_worker_queue(w)
        for wid in list(self._rings):
            self._retire_ring(wid)
        for w in self._workers.values():
            w["out_q"].close()
            w["out_q"].cancel_join_thread()
        self._workers.clear()
        self._stopped = True
        if self._metrics:
            self._metrics["workers"].set(0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def wait_ready(self, timeout: float = 30.0) -> bool:
        """Block until every live worker has announced its shared-memory
        ring — i.e. decoded its first batch and started prefilling
        slots. Keeps cold-start cost (spawn-method child imports, first
        decode) out of a latency-sensitive or measured first step.
        Returns False on timeout."""
        if not self._started:
            self.start()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            self._sweep()
            if all(w["finished"] or wid in self._rings
                   for wid, w in self._workers.items()):
                return True
            self._check_liveness()
            time.sleep(0.02)
        return False

    # -- cursor state --------------------------------------------------
    def _build_state(self, delivered: int, shards: dict,
                     totals: dict) -> dict:
        return {
            "v": 1,
            "delivered": delivered,
            "shards": {str(s): [e, b] for s, (e, b) in shards.items()},
            "totals": {str(s): t for s, t in totals.items()},
            "config": {"nshards": len(self.cfg.shards),
                       "batch_size": self.cfg.batch_size,
                       "seed": self.cfg.seed,
                       "shuffle_block_batches":
                           self.cfg.shuffle_block_batches,
                       "epochs": self.cfg.epochs},
        }

    def snapshot(self) -> dict:
        """Cursor state as of the last DELIVERED batch."""
        with self._snap_lock:
            return self._build_state(
                self._delivered,
                {s: (self._e[s], self._b[s])
                 for s in range(len(self.cfg.shards))},
                dict(self._totals))

    def state_for(self, delivered: int) -> dict:
        """Cursor state as of `delivered` batches handed out by THIS
        service instance — the Trainer checkpoints the state of its
        consumed count, which trails the prefetcher's pulls. The state
        is rebuilt by walking the per-delivery pointer log backwards
        from the live cursor; learned shard totals are time-invariant
        facts, so carrying them back is exact."""
        with self._snap_lock:
            now = self._delivered
            base = {s: (self._e[s], self._b[s])
                    for s in range(len(self.cfg.shards))}
            log = list(self._snap_log)
            totals = dict(self._totals)
        oldest = log[0][0] if log else now + 1
        if delivered > now or delivered < oldest - 1:
            raise KeyError(
                f"no reconstructable input state for "
                f"delivered={delivered} (current={now}, log reaches "
                f"back to {oldest - 1}; the last "
                f"{self._snap_log.maxlen} deliveries are retained)")
        for d, s, pe, pb in reversed(log):
            if d <= delivered:
                break
            base[s] = (pe, pb)
        return self._build_state(delivered, base, totals)

    def restore(self, state: dict):
        """Seed the delivery cursor from a checkpointed state. Must be
        called before the worker pool starts (i.e. before the first
        `reader()` pull)."""
        if self._started:
            raise RuntimeError(
                "restore() must run before the service starts — build a "
                "fresh StreamingInputService for a checkpoint resume")
        _check_state(self.cfg, state)
        for s_str, (e, b) in state["shards"].items():
            s = int(s_str)
            self._e[s], self._b[s] = int(e), int(b)
        self._totals = {int(s): int(t)
                        for s, t in state.get("totals", {}).items()}
        self._fin.clear()
        for s in range(len(self.cfg.shards)):
            if self._e[s] >= self.cfg.epochs:
                self._fin.add(s)
            self._advance(s)

    # -- delivery ------------------------------------------------------
    def reader(self):
        """Zero-arg reader (paddle convention): returns the iterator of
        remaining batches. Content/order are bit-identical to
        ``iter_stream`` at the same cursor, for any worker count."""
        if not self._started:
            self.start()
        return self._deliver()

    def _deliver(self):
        cfg = self.cfg
        nshards = len(cfg.shards)
        while True:
            if self._fatal is not None:
                raise self._fatal
            live = [s for s in range(nshards) if s not in self._fin]
            if not live:
                return
            s = min(live, key=lambda t: (self._e[t], self._b[t], t))
            tot = self._totals.get(s)
            if tot is not None and self._b[s] >= tot:
                with self._snap_lock:
                    self._advance(s)
                continue
            # ingest everything already readable so the occupancy the
            # scaler sees is the PRODUCED depth, not just what past
            # waits happened to pull in
            self._sweep()
            key = (self._e[s], self._b[s], s)
            starved = key not in self._buffer
            while key not in self._buffer:
                tot = self._totals.get(s)
                if tot is not None and self._b[s] >= tot:
                    break  # eof arrived while waiting: recompute shard
                self._pull()
            if key not in self._buffer:
                continue
            occ = len(self._buffer)
            arrays = self._materialize(self._buffer.pop(key))
            # pointer advance + delta log are atomic vs a concurrent
            # state_for() (the Trainer checkpoints from its own thread
            # while this generator runs on the prefetcher's)
            with self._snap_lock:
                prev = (self._e[s], self._b[s])
                self._b[s] += 1
                self._advance(s)
                self._delivered += 1
                self._snap_log.append(
                    (self._delivered, s, prev[0], prev[1]))
            self._account(starved, occ)
            yield _as_feed(cfg, arrays)

    def _materialize(self, entry):
        if entry[0] == "data":
            return entry[1]
        _, wid, slot = entry
        _shms, views, _label = self._rings[wid]
        arrays = tuple(np.array(v) for v in views[slot])
        w = self._workers.get(wid)
        if w is not None:
            w["free_q"].put(slot)
        return arrays

    def _advance(self, s):
        while s not in self._fin:
            tot = self._totals.get(s)
            if tot is None or self._b[s] < tot:
                return
            self._e[s] += 1
            self._b[s] = 0
            if self._e[s] >= self.cfg.epochs or tot == 0:
                self._fin.add(s)

    # -- queue plumbing ------------------------------------------------
    def _pull(self, timeout: float = 0.5):
        """Receive from every unfinished worker's own result queue.
        connection.wait on the queues' read pipes gives a blocking
        multi-queue select; a finished ("done" received) worker's queue
        is complete and dropped from the poll set, so its EOF'd pipe
        can't busy-spin the wait."""
        polled = {w["out_q"]._reader: w["out_q"]
                  for w in self._workers.values() if not w["finished"]}
        got = False
        if polled:
            for r in mp_connection.wait(list(polled), timeout):
                q = polled[r]
                while True:
                    try:
                        msg = q.get_nowait()
                    except (_queue.Empty, EOFError, OSError, ValueError):
                        # ValueError: _handle routed an "error" to
                        # _crash, which retired and closed this queue
                        break
                    got = True
                    self._handle(msg)
        else:
            time.sleep(min(timeout, 0.05))
        if not got or time.monotonic() - self._last_liveness > 1.0:
            self._check_liveness()

    def _sweep(self):
        """Non-blocking ingest of every unfinished worker's queue."""
        for w in list(self._workers.values()):
            if w["finished"]:
                continue
            while True:
                try:
                    msg = w["out_q"].get_nowait()
                except (_queue.Empty, EOFError, OSError, ValueError):
                    break
                self._handle(msg)

    def _drain_worker_queue(self, w, timeout: float = 0.05):
        """Process everything currently readable on one worker's queue
        (used before retiring its ring, so already-shipped batches are
        salvaged instead of re-decoded)."""
        while True:
            try:
                self._handle(w["out_q"].get(timeout=timeout))
            except (_queue.Empty, EOFError, OSError, ValueError):
                return

    def _handle(self, msg):
        kind, wid = msg[0], msg[1]
        if kind == "meta":
            _, _, names, layout = msg
            from multiprocessing import shared_memory
            shms = [shared_memory.SharedMemory(name=n) for n in names]
            if wid not in self._workers:
                # stale incarnation's ring: adopt only to unlink it
                for m in shms:
                    try:
                        m.unlink()
                    except FileNotFoundError:
                        pass
                    m.close()
                return
            views = []
            for m in shms:
                off, vs = 0, []
                for shape, dtype in layout:
                    a = np.frombuffer(
                        m.buf, dtype=np.dtype(dtype),
                        count=int(np.prod(shape, dtype=np.int64)),
                        offset=off).reshape(shape)
                    a.flags.writeable = False
                    vs.append(a)
                    off += a.nbytes
                views.append(tuple(vs))
            self._rings[wid] = (shms, views,
                                self._workers[wid]["label"])
        elif kind == "batch":
            _, _, s, e, b, slot = msg
            ring = self._rings.get(wid)
            if ring is None:
                return  # retired incarnation: will be re-produced
            key = (e, b, s)
            duplicate = (key in self._buffer or s in self._fin
                         or (e, b) < (self._e[s], self._b[s]))
            if duplicate:
                w = self._workers.get(wid)
                if w is not None:
                    w["free_q"].put(slot)
                return
            self._buffer[key] = ("slot", wid, slot)
            if self._metrics:
                self._metrics["batches"].labels(
                    worker=str(ring[2])).inc()
                self._metrics["occupancy"].set(len(self._buffer))
        elif kind == "eof":
            _, _, s, _e, total = msg
            self._totals.setdefault(s, int(total))
        elif kind == "error":
            _, _, tb = msg
            if wid in self._workers:
                self._crash(wid, tb)
        elif kind == "done":
            w = self._workers.get(wid)
            if w is not None:
                w["finished"] = True

    def _check_liveness(self):
        self._last_liveness = time.monotonic()
        for wid, w in list(self._workers.items()):
            if w.get("finished") or w["proc"].is_alive():
                continue
            # sweep its queue once: a clean exit's "done" (or a dying
            # worker's "error" — which _handle routes to _crash with
            # the real worker traceback) may still be in the pipe
            self._drain_worker_queue(w)
            if wid not in self._workers or \
                    self._workers[wid].get("finished"):
                continue
            self._crash(wid, f"worker process died with exit code "
                             f"{w['proc'].exitcode} (no farewell "
                             "message: killed or crashed hard)")

    # -- pool management -----------------------------------------------
    def _spawn_pool(self, n: int):
        n = max(1, min(n, self.cfg.max_workers, len(self.cfg.shards)))
        order = list(range(len(self.cfg.shards)))
        for i in range(n):
            self._spawn_worker(i, order[i::n])
        if self._metrics:
            self._metrics["workers"].set(len(self._workers))
            self._metrics["capacity"].set(
                len(self._workers) * self.cfg.slots_per_worker)

    def _spawn_worker(self, label: int, shard_list: List[int]):
        wid = self._next_wid
        self._next_wid += 1
        specs = [(s, self._e[s], self._b[s])
                 for s in shard_list if s not in self._fin]
        if not specs:
            # every assigned shard is already finished (restore near
            # end-of-stream, or a crash after its shards completed):
            # nothing to produce, so don't pay a worker process for it
            return
        free_q = self._ctx.Queue()
        out_q = self._ctx.Queue()
        proc = self._ctx.Process(
            target=_service_worker_main,
            args=(wid, specs, self.cfg, self.cfg.slots_per_worker,
                  free_q, out_q, self._stop_ev, os.getpid()),
            daemon=True)
        proc.start()
        self._workers[wid] = {"proc": proc, "free_q": free_q,
                              "out_q": out_q,
                              "shards": list(shard_list), "label": label,
                              "finished": False}

    def _retire_ring(self, wid: int):
        ring = self._rings.pop(wid, None)
        if ring is None:
            return
        shms, views, _label = ring
        for key, entry in list(self._buffer.items()):
            if entry[0] == "slot" and entry[1] == wid:
                self._buffer[key] = (
                    "data",
                    tuple(np.array(v) for v in views[entry[2]]))
        views = None
        ring = None
        for m in shms:
            try:
                m.close()
            except BufferError:
                m.__class__ = _EscapedSegment
            try:
                m.unlink()
            except FileNotFoundError:
                pass

    def _crash(self, wid: int, tb: str):
        w = self._workers.pop(wid)
        w["proc"].join(timeout=2.0)
        if w["proc"].is_alive():
            w["proc"].terminate()
            w["proc"].join(1.0)
        # salvage everything it managed to ship before dying (the
        # worker is already out of self._workers, so a queued "error"
        # can't recurse into _crash)
        self._drain_worker_queue(w)
        self._retire_ring(wid)
        w["out_q"].close()
        w["out_q"].cancel_join_thread()
        if self._stopped or self._stop_ev.is_set():
            # teardown (stop()/rescale) in progress: a straggling error
            # message must neither spawn an orphan into the dying pool
            # nor raise out of the caller's `finally: svc.stop()`
            return
        self._respawns += 1
        if self._metrics:
            self._metrics["respawns"].inc()
        if self._respawns > self.cfg.max_respawns:
            self._fatal = RuntimeError(
                f"streaming input worker crashed and the respawn budget "
                f"({self.cfg.max_respawns}) is exhausted; last failure:\n"
                f"{tb}")
            raise self._fatal
        time.sleep(self.cfg.respawn_delay_s)
        self._spawn_worker(w["label"], w["shards"])

    def _rescale(self, n: int, direction: str):
        old = list(self._workers.values())
        self._stop_ev.set()
        for w in old:
            w["proc"].join(timeout=5.0)
        for w in old:
            if w["proc"].is_alive():
                w["proc"].terminate()
                w["proc"].join(1.0)
        for w in old:
            self._drain_worker_queue(w)
        self._workers.clear()
        for wid in list(self._rings):
            self._retire_ring(wid)
        for w in old:
            w["out_q"].close()
            w["out_q"].cancel_join_thread()
        self._stop_ev = self._ctx.Event()
        self._scale_events[direction] += 1
        if self._metrics:
            self._metrics["scale"].labels(direction=direction).inc()
        self._spawn_pool(n)

    # -- elastic scaling + metrics --------------------------------------
    def _account(self, starved: bool, occ: int):
        self._win_deliv += 1
        self._win_starved += int(starved)
        self._win_min_occ = occ if self._win_min_occ is None \
            else min(self._win_min_occ, occ)
        if self._metrics:
            self._metrics["occupancy"].set(len(self._buffer))
            self._update_lag()
        cfg = self.cfg
        now = time.monotonic()
        if cfg.scale_interval_s <= 0 or \
                now - self._win_t0 < cfg.scale_interval_s or \
                self._win_deliv < 4:
            return
        n = len(self._workers)
        cap = n * cfg.slots_per_worker
        starved_frac = self._win_starved / self._win_deliv
        hi = min(cfg.max_workers, len(cfg.shards))
        if starved_frac > cfg.scale_up_starved and n < hi:
            self._rescale(n + 1, "up")
        elif self._win_starved == 0 and n > cfg.min_workers and \
                self._win_min_occ is not None and \
                self._win_min_occ >= cap - n:
            self._rescale(n - 1, "down")
        # window restarts AFTER any rescale (which blocks for the pool
        # restart): anchoring it to the pre-rescale timestamp would
        # expire the next window immediately, and the cold new pool's
        # first starved deliveries would cascade another rescale
        self._win_t0 = time.monotonic()
        self._win_deliv = 0
        self._win_starved = 0
        self._win_min_occ = None

    def _update_lag(self):
        # shard lag in delivered batches, against the most advanced
        # shard (absolute = epoch * total + next_batch once the epoch
        # size is known; before that, next_batch alone)
        def absol(s):
            tot = self._totals.get(s)
            return (self._e[s] * tot + self._b[s]) if tot is not None \
                else self._b[s]

        vals = {s: absol(s) for s in range(len(self.cfg.shards))}
        top = max(vals.values(), default=0)
        for s, v in vals.items():
            self._metrics["lag"].labels(shard=str(s)).set(top - v)

    def _init_metrics(self):
        from ..observability.registry import default_registry
        reg = default_registry()
        if not reg.enabled:
            self._metrics = None
            return
        self._metrics = {
            "batches": reg.counter(
                "paddle_tpu_input_batches_total",
                "Batches produced by streaming input workers (labelled "
                "by worker pool slot).", ("worker",)),
            "occupancy": reg.gauge(
                "paddle_tpu_input_queue_occupancy",
                "Produced-but-undelivered batches buffered in the "
                "streaming input service (live prefetch-queue depth; "
                "the elastic-scaling signal)."),
            "capacity": reg.gauge(
                "paddle_tpu_input_queue_capacity",
                "Streaming input buffer capacity: workers x "
                "slots_per_worker shared-memory ring slots."),
            "workers": reg.gauge(
                "paddle_tpu_input_workers",
                "Current streaming input worker-process count."),
            "scale": reg.counter(
                "paddle_tpu_input_scale_events_total",
                "Elastic worker-pool rescale events.", ("direction",)),
            "respawns": reg.counter(
                "paddle_tpu_input_worker_respawns_total",
                "Streaming input workers respawned after a crash."),
            "lag": reg.gauge(
                "paddle_tpu_input_shard_lag",
                "Delivered-batch lag of each shard behind the most "
                "advanced shard.", ("shard",)),
        }

    # -- introspection --------------------------------------------------
    @property
    def delivered(self) -> int:
        return self._delivered

    def stats(self) -> dict:
        return {
            "delivered": self._delivered,
            "workers": len(self._workers),
            "respawns": self._respawns,
            "scale_events": dict(self._scale_events),
            "buffered": len(self._buffer),
            "totals": dict(self._totals),
            "finished_shards": sorted(self._fin),
        }
