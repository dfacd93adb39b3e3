"""Multi-process batch pipeline over shared memory.

The TPU-native answer to the reference's multi-threaded C++ file
readers (reference: paddle/fluid/operators/reader/open_files_op.cc —
N prefetch threads behind a blocking queue) and the multi-process leg
of its reader decorators (python/paddle/reader/decorator.py:236
xmap_readers): decode work that the GIL would serialize in threads
runs in worker PROCESSES, and finished batches cross back through
preallocated shared-memory ring slots — two queue messages per batch,
zero pickling of the payload.

Design:
- `worker_fn(worker_idx, num_workers, **kwargs)` is a module-level
  callable returning an iterator of tuple-of-ndarrays batches with
  FIXED shapes/dtypes (drop the last partial batch). It runs inside
  each worker process; under the default "spawn" start method it must
  be picklable by reference (a module-level function).
- Each worker allocates its own ring of `slots_per_worker` SHM blocks
  sized to its first batch, announces them on the shared result queue
  (so the announcement orders before any batch from that worker), then
  streams: free slot id in, batch bytes into the slot, (worker, slot)
  out.
- The consumer yields numpy VIEWS into the slot; a view is valid until
  the next `next()` — the consumer's device-put (or copy) must happen
  before advancing. The slot is handed back to its owner right before
  the next result is fetched.

Start method: "spawn" by default — fork would duplicate the parent's
JAX runtime threads and socket fds into children that only need numpy
(a held allocator lock at fork time deadlocks the child). Tests use
"fork" where worker closures are module-local and no device runtime is
live.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import queue as _queue
import traceback
import uuid
from multiprocessing import shared_memory
from typing import Callable, Dict, Optional

import numpy as np

__all__ = ["multiprocess_batch_reader", "new_shm_segment",
           "ensure_resource_tracker", "SHM_PREFIX"]

#: all shared-memory segments this package creates carry this prefix plus
#: the CONSUMER process pid, so tests (and operators) can audit
#: /dev/shm/ptshm<pid>_* for leaks attributable to one process.
SHM_PREFIX = "ptshm"


def new_shm_segment(size: int, consumer_pid: int) -> shared_memory.SharedMemory:
    """Create an auditable shared-memory segment: named
    ptshm<consumer_pid>_<uuid> rather than the stdlib's anonymous psm_*,
    so a leak is attributable to its owning reader process."""
    name = f"{SHM_PREFIX}{consumer_pid}_{uuid.uuid4().hex[:12]}"
    return shared_memory.SharedMemory(create=True, name=name,
                                      size=max(size, 1))


def ensure_resource_tracker() -> None:
    """Start multiprocessing's resource-tracker daemon from the
    CONSUMER process before any worker forks. Without this, the first
    shared-memory registration happens inside a worker, which lazily
    starts the tracker as *that worker's* child — the consumer then
    starts a second tracker and the two ledgers disagree: one reports
    the other's properly-unlinked segments as leaked at shutdown (and a
    SIGKILLed worker's tracker dies with it). One tracker, started
    here, makes every register/unregister land in one ledger where
    create-side and attach-side registrations dedupe (bpo-39959) and
    the single successful unlink balances them."""
    try:
        from multiprocessing import resource_tracker
        resource_tracker.ensure_running()
    except (ImportError, AttributeError, OSError):
        pass

class _EscapedSegment(shared_memory.SharedMemory):
    """Consumer-side segment a yielded view escaped into user code:
    close() would raise BufferError until the view dies, including from
    __del__ at interpreter shutdown ("Exception ignored" noise). The
    mapping is already unlinked; letting the OS reclaim it at process
    exit is the correct end state, so close() failures go silent."""

    def close(self):  # noqa: D102
        try:
            super().close()
        except BufferError:
            pass


def _worker_main(worker_fn, widx, nworkers, slots, free_q, full_q,
                 stop_ev, kwargs, consumer_pid):
    shms = []
    layout = None
    try:
        it = worker_fn(widx, nworkers, **(kwargs or {}))
        for batch in it:
            if stop_ev.is_set():
                break
            arrays = tuple(np.ascontiguousarray(a) for a in batch)
            if layout is None:
                layout = [(a.shape, str(a.dtype)) for a in arrays]
                total = sum(a.nbytes for a in arrays)
                for _ in range(slots):
                    shms.append(new_shm_segment(total, consumer_pid))
                full_q.put(("meta", widx, [s.name for s in shms], layout))
                for i in range(slots):
                    free_q.put(i)
            # wait for a slot the consumer has released
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
            # frombuffer arrays export pointers into the shm mapping;
            # a live export makes shm.close() raise BufferError later
            del dst, buf
            full_q.put(("batch", widx, slot))
    except BaseException as e:  # noqa: BLE001 — re-raised in the consumer
        try:
            # ship the full worker-side traceback: the consumer raises
            # it verbatim, so a decode bug points at the worker's frame,
            # not at an opaque queue read
            full_q.put(("error", widx, repr(e)[:500],
                        traceback.format_exc()[-4000:]))
        except BaseException:
            pass
    finally:
        try:
            # keep the ring alive until every slot id is back in free_q
            # (the consumer holds views into outstanding slots). Each id
            # is in free_q or held by the consumer and never re-enters
            # after a pop here, so popping `slots` ids total means all
            # returned — counting qsize() first would double-count the
            # already-queued ones.
            returned = 0
            while shms and returned < slots and not stop_ev.is_set():
                try:
                    free_q.get(timeout=0.2)
                    returned += 1
                except _queue.Empty:
                    if stop_ev.is_set():
                        break
            for s in shms:
                try:
                    s.close()
                except BufferError:
                    pass
                try:
                    s.unlink()
                except FileNotFoundError:
                    pass
        except BaseException:
            pass
        # ALWAYS announce exit — a missing "done" hangs the consumer
        full_q.put(("done", widx))


def multiprocess_batch_reader(worker_fn: Callable, num_workers: int,
                              slots_per_worker: int = 4,
                              method: str = "spawn",
                              worker_kwargs: Optional[dict] = None):
    """Reader factory: `reader()` yields tuple-of-ndarray batches
    produced by `num_workers` processes each running
    `worker_fn(worker_idx, num_workers, **worker_kwargs)`.

    ALIASING HAZARD: yielded arrays are READ-ONLY views into a
    shared-memory slot the producer overwrites once the consumer
    advances — they are valid only until the next `next()`. Callers
    that accumulate batches (e.g. for a later concat) must copy:
    `tuple(a.copy() for a in batch)`. The views are marked
    non-writeable so accidental in-place mutation raises instead of
    racing the producer. Closing the generator shuts the workers
    down.

    ONE PROCESS PER CHIP: the workers move numpy arrays through shared
    memory and never touch a jax array, and `worker_fn` must keep it
    so. The chip belongs to the process that first initialised a JAX
    backend (the trainer); a worker that created one jax array on a
    machine with a chip would hang or die on the TPU library's lock.
    `import paddle_tpu` initialises no backend."""
    if num_workers < 1:
        raise ValueError("num_workers must be >= 1")

    def reader():
        ensure_resource_tracker()
        ctx = mp.get_context(method)
        full_q = ctx.Queue()
        free_qs = [ctx.Queue() for _ in range(num_workers)]
        stop_ev = ctx.Event()
        procs = [
            ctx.Process(
                target=_worker_main,
                args=(worker_fn, w, num_workers, slots_per_worker,
                      free_qs[w], full_q, stop_ev, worker_kwargs,
                      os.getpid()),
                daemon=True)
            for w in range(num_workers)]
        for p in procs:
            p.start()
        rings: Dict[int, tuple] = {}  # widx -> (shms, views-per-slot)
        active = num_workers
        release = None  # (widx, slot) the consumer is done with
        try:
            dead_checked: set = set()
            while active:
                if release is not None:
                    free_qs[release[0]].put(release[1])
                    release = None
                try:
                    msg = full_q.get(timeout=2.0)
                except _queue.Empty:
                    # a worker killed without a farewell (OOM, SIGKILL,
                    # os._exit mid-stream) would otherwise stall this
                    # get forever: its "done"/"error" never arrives
                    for w, p in enumerate(procs):
                        if w not in dead_checked and not p.is_alive():
                            dead_checked.add(w)
                            active -= 1
                            if p.exitcode not in (0, None):
                                raise RuntimeError(
                                    f"reader worker {w} died with exit "
                                    f"code {p.exitcode} without "
                                    "reporting an error (killed or "
                                    "crashed hard); in-flight batches "
                                    "from it are lost")
                    continue
                kind = msg[0]
                if kind == "done":
                    # the liveness sweep may have already counted this
                    # worker out (its exit raced the message delivery)
                    if msg[1] not in dead_checked:
                        dead_checked.add(msg[1])
                        active -= 1
                elif kind == "error":
                    raise RuntimeError(
                        f"reader worker {msg[1]} failed: {msg[2]}\n"
                        f"--- worker traceback ---\n{msg[3]}")
                elif kind == "meta":
                    _, widx, names, layout = msg
                    shms = [shared_memory.SharedMemory(name=n)
                            for n in names]
                    views = []
                    for shm in shms:
                        off, vs = 0, []
                        for shape, dtype in layout:
                            a = np.frombuffer(
                                shm.buf, dtype=np.dtype(dtype),
                                count=int(np.prod(shape, dtype=np.int64)),
                                offset=off).reshape(shape)
                            # consumers must not mutate the producer's
                            # slot in place (see factory docstring)
                            a.flags.writeable = False
                            vs.append(a)
                            off += a.nbytes
                        views.append(tuple(vs))
                    rings[widx] = (shms, views)
                else:
                    _, widx, slot = msg
                    yield rings[widx][1][slot]
                    release = (widx, slot)
        finally:
            stop_ev.set()
            # np.frombuffer views hold exported pointers into shm.buf;
            # they must be dropped before close() or BufferError
            for widx, (shms, views) in rings.items():
                del views
                rings[widx] = (shms, None)
            release = None
            for p in procs:
                p.join(timeout=5)
            for p in procs:
                if p.is_alive():
                    p.terminate()
            for shms, _ in rings.values():
                for s in shms:
                    try:
                        s.unlink()
                    except FileNotFoundError:
                        pass
                    try:
                        s.close()
                    except BufferError:
                        s.__class__ = _EscapedSegment

    return reader
