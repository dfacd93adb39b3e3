"""Driver kind ``train``: the program's Trainer, fed by its
StreamingInputService from seeded recordio shards, on one chip through
``Executor`` or on a mesh through ``ParallelExecutor``.

One ``Trainer.train`` call runs the warm-up steps and the measured
window back to back, so the input pipeline never restarts: the window
opens when the last warm-up step's loss has been fetched and closes
with the first step that ends after ``seconds``. Every step ends in a
materialised loss (``log_every=1``), so a step's end is the device's.
"""
from __future__ import annotations

import math
import time

import numpy as np

from .. import reference, weights
from . import resolve, sizes
from ..traffic import train as traffic

# What every train cell has used so far; a cell's ``traffic`` may give
# any of them another value. ``shard_batches`` batches in each of
# ``shards`` recordio files, read by ``workers`` spawned workers,
# ``prefetch`` feeds ahead; 3 warm-up steps (on a mesh the first two
# may compile); the references go through this many tokens at a time.
DEFAULTS = {"shards": 2, "shard_batches": 8, "workers": 2, "prefetch": 2,
            "warmup_steps": 3, "reference_chunk_tokens": 4096,
            "gradient_chunk_tokens": 2048, "check_update": False}

# AMP bf16 against the f32 "highest" reference, one forward loss: a mean
# over >= 16k tokens of per-token losses near ln(vocab). Freshly drawn
# weights give logits much smaller than 1, so bf16 rounding moves each
# token's loss little and the mean less: the chip read relative
# differences of 2e-7 to 4.5e-6 in 51 runs of the three cells (PERF.md,
# PR 24). 5e-5 is ten times the largest and far under what a dropped
# mask, position table or layer gives (1e-2 and more at these sizes).
# A rehearsal's toy widths give larger logits and bf16 on the CPU 2e-4.
# Fresh weights make the forward loss a weak witness of the arithmetic
# (it barely feels an 8-bit matmul): the update check below is the
# stronger one.
LOSS_RTOL = {False: 5e-5, True: 5e-4}
# The first step's update against the reference's (``check_update``):
# the share of the reference Adam step's first-order descent that the
# applied update buys (reference.descent_share), over all parameters
# together and for each array. bf16 gradients flip the sign of
# elements whose gradient is within their rounding, which costs a few
# per cent: the chip read 0.997 over all parameters in both one-chip
# cells, and for the lowest of the 184 arrays 0.98 at sequence 256 and
# 0.82 at 2048 (a query/key projection, whose gradient is tiny at
# fresh weights) (PERF.md, PR 24). An update from a wrong gradient
# scores about 0, one that climbs -1.
UPDATE_SHARE_MIN = 0.9
UPDATE_SHARE_MIN_PER_ARRAY = 0.5


def _build_trainer(ctx, model: dict, seq: int):
    """(trainer, executor, main program) of the cell, started: weights
    drawn on the device by the startup program and re-drawn from --seed
    (weights.py). A mesh cell goes through ``ParallelExecutor`` from
    the startup program on, so the state is born sharded."""
    import paddle_tpu as pt
    from paddle_tpu.trainer import Trainer
    pt.reset_default_programs()
    pt.reset_global_scope()
    pt.amp.enable(ctx.config["precision"] == "amp_bf16")
    build = resolve(ctx.config["builder"]["function"])
    main, startup, fetch = build(**dict(model, max_len=seq))
    mesh_cfg = ctx.workload.get("mesh")
    if mesh_cfg:
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.parallel import make_mesh
        from paddle_tpu.parallel.executor import (ParallelExecutor,
                                                  ShardingSpec)
        mesh = make_mesh(tuple(mesh_cfg["shape"]), tuple(mesh_cfg["axes"]),
                         devices=ctx.devices)
        sharding = ShardingSpec(
            specs=resolve(mesh_cfg["param_specs"])(main),
            feed_axis=mesh_cfg["feed_axis"])
        for name in mesh_cfg.get("replicated_feeds", []):
            sharding.specs[name] = P()
        exe = ParallelExecutor(mesh=mesh, sharding=sharding)
    else:
        exe = pt.Executor(pt.TPUPlace() if not ctx.rehearse else None)
    trainer = Trainer(fetch["loss"], main, startup, executor=exe)
    ctx.phase("programs built")
    trainer.start()
    weights.reseed(pt.global_scope(), main.all_parameters(), ctx.seed)
    ctx.phase("startup program ran, weights from the seed")
    return trainer, exe, main


def run(ctx) -> dict:
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.reader import (RawDecoder, StreamingConfig,
                                   StreamingInputService)
    from paddle_tpu.trainer import BeginIteration, EndIteration

    model, tr = sizes(ctx.config, ctx.workload, ctx.rehearse, DEFAULTS)
    ref, cost = ctx.config["reference"], ctx.config["arith"]
    batch, seq = int(tr["batch"]), int(tr["seq"])
    vocab = int(model["trg_vocab"])
    warm = int(tr["warmup_steps"])
    check_update = bool(tr["check_update"])
    tokens_per_step = batch * seq

    paths = traffic.write_shards(
        ctx.workdir, ctx.seed, batch, seq, vocab,
        int(tr["shards"]), int(tr["shard_batches"]))

    ctx.phase("shards written")
    scfg = StreamingConfig(
        shards=paths, batch_size=batch,
        decode=RawDecoder([((seq, 1), "int64")] * traffic.FIELDS),
        collate=traffic.collate_with_positions,
        feed_names=traffic.FEED_NAMES, epochs=1_000_000,
        workers=int(tr["workers"]), min_workers=int(tr["workers"]),
        max_workers=int(tr["workers"]))
    state = {"done": False, "t_begin": 0.0, "t0": None, "first": None,
             "snapshot": None, "misses": None, "feed0": None, "w1": None}
    steps = []                          # (begin, end, loss) host seconds

    def reader(service):
        def gen():
            for i, feed in enumerate(service.reader()):
                if state["done"]:
                    return
                if i == warm or (i == 0 and check_update):
                    # the first measured batch; the very first batch
                    state["first" if i else "feed0"] = {
                        k: np.array(v) for k, v in feed.items()}
                yield feed
        return gen

    def handler(ev):
        if isinstance(ev, BeginIteration):
            state["t_begin"] = time.perf_counter()
        elif isinstance(ev, EndIteration):
            loss = float(ev.cost)       # already fetched: log_every=1
            now = time.perf_counter()
            steps.append((state["t_begin"], now, loss))
            if len(steps) == 1:
                if check_update:        # the weights after one update
                    state["w1"] = [np.asarray(scope.get(n))
                                   for n in param_names]
                ctx.phase("first step done")
            if len(steps) == warm:
                # the weights the first measured step will read, kept
                # for the reference (device copies; the step donates
                # the originals)
                state["snapshot"] = [jnp.copy(scope.get(n))
                                     for n in param_names]
                state["misses"] = exe.cache_stats["misses"]
                state["t0"] = ctx.open_window()
            elif state["t0"] is not None and not state["done"] and \
                    now >= state["t0"] + ctx.seconds:
                state["done"] = True
                state["t1"] = now

    # the workers are numpy-only children: they import and fill their
    # rings while this process builds and starts the program
    with StreamingInputService(scfg) as svc:
        svc.start()
        trainer, exe, main = _build_trainer(ctx, model, seq)
        param_names = [p.name for p in main.all_parameters()]
        scope = pt.global_scope()
        w0 = [np.asarray(scope.get(n)) for n in param_names] \
            if check_update else None
        if not svc.wait_ready(180.0):
            raise RuntimeError("streaming workers did not come up")
        ctx.phase("input workers ready")
        trainer.train(1, reader(svc), event_handler=handler,
                      prefetch=int(tr["prefetch"]), log_every=1)
        input_stats = svc.stats()
    t0, t1 = state["t0"], state["t1"]
    ctx.close_window(t1)

    measured = [s for s in steps[warm:] if s[1] <= t1]
    losses = [s[2] for s in measured]
    compiles_in_window = ctx.spans.compile_count(t0, t1)
    new_misses = exe.cache_stats["misses"] - state["misses"]

    # -- outside the window: the reference, memory -----------------------
    tape = [np.asarray(a) for a in state["snapshot"]]
    state["snapshot"] = None
    ref_loss = resolve(ref["loss"])(
        tape, state["first"], model, int(tr["reference_chunk_tokens"]))
    del tape
    first_loss = measured[0][2] if measured else float("nan")
    rtol = LOSS_RTOL[ctx.rehearse]
    loss_ok = abs(first_loss - ref_loss) <= rtol * abs(ref_loss)
    finite = all(math.isfinite(x) for x in losses)
    update = _check_update(w0, state["w1"], state["feed0"], model, ref,
                           int(tr["gradient_chunk_tokens"]),
                           param_names) if check_update else None
    correct = bool(measured and finite and loss_ok
                   and (update is None or update["ok"])
                   and compiles_in_window == 0 and new_misses == 0)
    t_mem = time.perf_counter()
    temp_bytes = _program_temp_bytes(exe, main)
    t_mem = time.perf_counter() - t_mem
    exe.close()
    pt.amp.enable(False)

    first_calls = [(b, e) for b, e, _l in steps[:warm]]
    ctx.run.update(
        kind="train", steps=measured, tokens_per_step=tokens_per_step,
        first_calls=first_calls, input_stats=input_stats,
        step_flops=resolve(cost["train_flops"])(batch, seq, **model),
        # under a mesh each device runs its share of the batch and of
        # the heads: the per-device kernel work is the global over chips
        flash_cost={k: v / ctx.chips for k, v in resolve(
            cost["flash_cost"])(batch, seq, **model).items()})
    ctx.note(check=dict(first_loss=first_loss, reference_loss=ref_loss,
                        rel_diff=abs(first_loss - ref_loss)
                        / abs(ref_loss), rtol=rtol,
                        losses_finite=finite, update=update,
                        compiles_in_window=compiles_in_window,
                        executor_misses_in_window=new_misses),
             steps=len(measured), step_s_median=float(np.median(
                 [e - b for b, e, _l in measured])) if measured else None,
             # a stall shows as one long gap between step ends
             step_end_gap_s_max=float(np.max(np.diff(
                 [t0] + [e for _b, e, _l in measured])))
             if measured else None,
             first_step_walls=[e - b for b, e in first_calls],
             memory=dict(program_temp_bytes=temp_bytes,
                         reading_it_took_s=t_mem,
                         allocator=ctx.devices[0].memory_stats()),
             input=dict(delivered=input_stats.get("delivered"),
                        respawns=input_stats.get("respawns")))
    n = len(measured)
    return {
        "correct": correct, "attempted": n,
        "failed": sum(1 for x in losses if not math.isfinite(x)),
        "end_to_end": {"train_tokens_per_s":
                       n * tokens_per_step / (t1 - t0) if n else 0.0},
        "program_temp_bytes": temp_bytes,
    }


def _check_update(w0, w1, feed0, model, ref, chunk_tokens, names) -> dict:
    """The first training step's update against the reference's: the
    gradient of the plain f32 forward on the first batch at the first
    weights, through the first step of the configuration's optimizer.
    Holds the backward pass and the optimizer to the reference, which
    the forward loss cannot."""
    import jax.numpy as jnp
    lr = float(model["lr"])
    grads = resolve(ref["grads"])(w0, feed0, model, chunk_tokens)
    wanted = resolve(ref["first_update"])(grads, lr)
    applied = [jnp.asarray(b) - jnp.asarray(a) for a, b in zip(w0, w1)]
    share = reference.descent_share(grads, applied, wanted)
    largest = max(float(jnp.max(jnp.abs(a))) for a in applied)
    scored = sorted((s, n) for s, n in zip(share["per_array"], names)
                    if s is not None)
    ok = (share["overall"] is not None
          and share["overall"] >= UPDATE_SHARE_MIN
          and scored[0][0] >= UPDATE_SHARE_MIN_PER_ARRAY
          and scored[-1][0] <= 2.0 - UPDATE_SHARE_MIN_PER_ARRAY
          and largest <= 1.01 * lr)
    return {"ok": bool(ok), "descent_share": share["overall"],
            "lowest": scored[:6], "highest": scored[-1],
            "arrays_scored": len(scored), "arrays": len(names),
            "largest_change_over_lr": largest / lr}


def _program_temp_bytes(exe, main) -> int:
    """Per-device temporaries XLA planned inside the step program, from
    ``memory_analysis`` of the executor's own cache entry lowered again
    (the repo's helper; the persistent cache answers the compile)."""
    from paddle_tpu.parallel.collective_audit import aot_compiled_for
    try:
        ma = aot_compiled_for(exe, main).memory_analysis()
        return int(ma.temp_size_in_bytes)
    except Exception:  # noqa: BLE001 — a reading, never a run's failure
        return 0
