#!/usr/bin/env python
"""A/B harness for the in-place buffer-reuse pass (analysis/rewrite.py
InplaceBufferReuse) against the static memory planner
(analysis/memory.py): reuse OFF vs ON, same program, same feeds.

Arms (per program):
  off  PADDLE_TPU_INPLACE_REUSE=0 — the full rewrite pipeline runs
       (DCE/CSE/outlining/dispatch) but every var keeps its own buffer;
  on   PADDLE_TPU_INPLACE_REUSE=1 (the default) — dead-interval
       activations fold into compatible predecessor buffers.

Programs:
  transformer_s2048  composed-attention transformer train graph at
                     seq 2048 — the activation-dominated regime the
                     pass exists for;
  transformer_s4096  same at seq 4096 (activation bytes scale ~4x);
  decode_step        the decoder-LM single-token decode program
                     (cache-resident regime: persistable KV state
                     dominates and is reuse-ineligible by design).

The static section reports, per arm, the planner's arena peak
(MemoryReport.peak_bytes with real feed shapes), the ideal-allocator
bound, and ``peak_reduction_pct`` — the headline the pre-compile OOM
gate experiences. The optional timing section (skipped by --static-only)
runs bench.py's marginal-cost protocol per arm with its
repeat-and-report-spread convention (median of --repeats marginal
estimates, spread_pct = 100*(max-min)/median): buffer renaming happens
before XLA sees the graph, so steps/sec should be flat — the timing arm
exists to prove the reduction is free, not to claim a speedup.

Off-TPU the static numbers are exact (no compile involved); run with
--smoke for tiny-shape CI coverage of the whole protocol.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _set_arm(arm: str):
    os.environ["PADDLE_TPU_INPLACE_REUSE"] = "0" if arm == "off" else "1"


def _transformer_build(args, seq_len):
    from paddle_tpu.models import transformer as tm

    def build():
        main, startup, fetches = tm.build_train(
            src_vocab=args.vocab, trg_vocab=args.vocab,
            max_len=seq_len, n_layer=args.n_layer, n_head=args.n_head,
            d_model=args.d_model, d_inner=args.d_inner,
            attention_impl="composed")
        feed_names = ["src_ids", "trg_ids", "trg_labels", "pos_ids"]
        return main, startup, feed_names, [fetches["loss"].name]
    return build


def _decode_build(args):
    from paddle_tpu.models.transformer import build_decoder_lm

    def build():
        programs = build_decoder_lm(
            vocab_size=args.vocab, max_seq_len=args.decode_seq,
            slots=args.decode_slots,
            prompt_buckets=[args.decode_seq],
            cache_buckets=[args.decode_seq], n_layer=args.n_layer,
            n_head=args.n_head, d_model=args.d_model,
            d_inner=args.d_inner)
        bucket = max(programs["decode"])
        lm = programs["decode"][bucket]
        return lm.main, programs["startup"], list(lm.feed_names), \
            [lm.fetch_name]
    return build


def static_ab(build, batch, label):
    """Rewrite + plan one program under both arms; returns the per-arm
    peaks, the reuse action summary, and ``peak_reduction_pct``.

    Each arm rebuilds from scratch so the OFF arm's pipeline never sees
    renamed vars; the memory plan binds -1 dims to ``batch`` (the
    executor's gate binds real feed shapes the same way)."""
    from paddle_tpu.analysis import memory, rewrite
    entry = {}
    for arm in ("off", "on"):
        _set_arm(arm)
        main, _startup, feed_names, fetch_names = build()
        t0 = time.time()
        res = rewrite.rewrite_program(main, feed_names=feed_names,
                                      fetch_names=fetch_names)
        mem = memory.program_memory(res.program, batch=batch,
                                    feed_names=feed_names,
                                    label=f"{label} reuse={arm}")
        entry[arm] = {
            "peak_bytes": mem.peak_bytes,
            "ideal_peak_bytes": mem.ideal_peak_bytes,
            "resident_bytes": mem.resident_bytes,
            "activation_bytes": mem.activation_bytes,
            "n_buffers": len(mem.intervals),
            "high_water": mem.high_water,
            "reuse_actions": res.count(pass_name="inplace_reuse"),
            "rewrite_aborted": list(res.aborted),
            "wall_s": round(time.time() - t0, 2),
        }
    _set_arm("on")
    off, on = entry["off"]["peak_bytes"], entry["on"]["peak_bytes"]
    entry["peak_reduction_pct"] = round(100.0 * (off - on)
                                        / max(off, 1), 1)
    entry["reuse_bytes"] = off - on
    return entry


def timed_ab(build, feed, args):
    """steps/sec per arm (marginal-cost protocol); reuse engages via
    the executor's own rewrite pipeline here, not an offline call."""
    import paddle_tpu as pt
    from bench import _marginal_steps_per_sec
    entry = {}
    for arm in ("off", "on"):
        _set_arm(arm)
        main, startup, _feed_names, fetch_names = build()
        loss_name = fetch_names[0]
        scope = pt.Scope()
        exe = pt.Executor()
        with pt.scope_guard(scope):
            exe.run(startup)
            sps, spread = _marginal_steps_per_sec(
                exe, main, feed, loss_name, n1=args.skip_batch_num,
                n2=args.iterations, repeats=args.repeats)
            losses = [float(np.ravel(np.asarray(exe.run(
                main, feed=feed, fetch_list=[loss_name])[0]))[0])
                for _ in range(3)]
        entry[arm] = {"steps_per_sec": round(sps, 4),
                      "spread_pct": round(100.0 * spread, 1),
                      "losses_3steps": losses}
    _set_arm("on")
    entry["speedup"] = round(
        entry["on"]["steps_per_sec"]
        / max(entry["off"]["steps_per_sec"], 1e-9), 3)
    entry["loss_max_abs_diff"] = max(
        abs(a - b) for a, b in zip(entry["off"]["losses_3steps"],
                                   entry["on"]["losses_3steps"]))
    return entry


def _transformer_feed(args, seq_len, rng):
    ids = rng.randint(1, args.vocab,
                      size=(args.batch, seq_len, 1)).astype(np.int64)
    return {"src_ids": ids, "trg_ids": ids, "trg_labels": ids,
            "pos_ids": np.arange(seq_len, dtype=np.int64)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=4000)
    ap.add_argument("--n-layer", type=int, default=2)
    ap.add_argument("--n-head", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--d-inner", type=int, default=2048)
    ap.add_argument("--decode-seq", type=int, default=256)
    ap.add_argument("--decode-slots", type=int, default=8)
    ap.add_argument("--seq-lens", default="2048,4096",
                    help="transformer sequence lengths to plan")
    ap.add_argument("--iterations", type=int, default=20)
    ap.add_argument("--skip_batch_num", type=int, default=5)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--static-only", action="store_true",
                    help="skip the steps/sec timing arms (static "
                         "planning needs no compile and is exact "
                         "off-TPU)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + 1 repeat: protocol/CI check, "
                         "not a perf number")
    ap.add_argument("--json", help="write the report here (default "
                                   "stdout only)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.batch, args.vocab = 2, 64
        args.n_layer, args.n_head = 1, 2
        args.d_model, args.d_inner = 32, 64
        args.decode_seq, args.decode_slots = 32, 2
        args.seq_lens = "16,32"
        args.iterations, args.skip_batch_num, args.repeats = 4, 1, 1

    seq_lens = [int(s) for s in args.seq_lens.split(",") if s.strip()]
    rng = np.random.RandomState(0)
    report = {"config": {k: getattr(args, k) for k in
                         ("batch", "vocab", "n_layer", "n_head",
                          "d_model", "d_inner", "decode_seq",
                          "decode_slots", "seq_lens", "smoke")},
              "programs": {}}
    specs = [(f"transformer_s{s}", _transformer_build(args, s), s)
             for s in seq_lens]
    specs.append(("decode_step", _decode_build(args), None))

    for name, build, seq_len in specs:
        entry = {"static": static_ab(build, args.batch, name)}
        st = entry["static"]
        print(f"{name:18s} peak off {st['off']['peak_bytes']:>14,} B  "
              f"on {st['on']['peak_bytes']:>14,} B  "
              f"reduction {st['peak_reduction_pct']:5.1f}%  "
              f"({st['on']['reuse_actions']} reuses)", flush=True)
        if not args.static_only and seq_len is not None:
            feed = _transformer_feed(args, seq_len, rng)
            entry["timing"] = timed_ab(build, feed, args)
        report["programs"][name] = entry
    _set_arm("on")
    os.environ.pop("PADDLE_TPU_INPLACE_REUSE", None)
    out = json.dumps(report, indent=2)
    if args.json:
        with open(args.json, "w") as f:
            f.write(out)
        print(f"wrote {args.json}")
    else:
        print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
