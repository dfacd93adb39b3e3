#!/usr/bin/env python
"""A/B harness for the ProgramDesc rewrite layer (analysis/rewrite.py):
optimize OFF vs ON, same model, same feeds, same protocol.

Arms (per model):
  off  PADDLE_TPU_OPTIMIZE=0 and every Pallas dispatch knob pinned to
       "0" — the program compiles exactly as the user built it, no
       hand kernels (the honest "unoptimized user program" baseline);
  on   PADDLE_TPU_OPTIMIZE=1 with default knobs — the rewrite pipeline
       outlines/annotates and the kernels engage where profitable.

Models:
  transformer  composed-attention transformer (the matmul->softmax->
               matmul chain the fusion outlining exists for) at
               --seq-len (default 2048); reports tokens/sec
               (batch * seq).
  lstm_lm      the stacked-LSTM language model (ragged feeds); reports
               tokens/sec (fed tokens per step).

Timing is bench.py's marginal-cost protocol with its
repeat-and-report-spread convention (median of `--repeats` marginal
estimates, spread_pct = (max-min)/median — estimates whose spread
swamps the delta are flagged, not trusted). The JSON also reports the
compile-path rewrite overhead (pipeline wall seconds + per-pass action
counts) and a DCE/CSE sweep over the 9 lint_ir networks under the
training (loss-only) fetch stance.

Off-TPU this runs with --smoke shapes: the protocol and the rewrite
engage, but the perf numbers only mean something on the chip.
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

#: dispatch knobs the OFF arm pins to "0" (no hand kernels at all)
_KERNEL_KNOBS = ("PADDLE_TPU_PALLAS_LSTM", "PADDLE_TPU_PALLAS_GRU",
                 "PADDLE_TPU_PALLAS_SDPA")


def _set_arm(arm: str):
    if arm == "off":
        os.environ["PADDLE_TPU_OPTIMIZE"] = "0"
        for k in _KERNEL_KNOBS:
            os.environ[k] = "0"
    else:
        os.environ["PADDLE_TPU_OPTIMIZE"] = "1"
        for k in _KERNEL_KNOBS:
            os.environ.pop(k, None)


def _transformer_build(args):
    from paddle_tpu.models import transformer as tm
    return lambda: tm.build_train(
        src_vocab=args.vocab, trg_vocab=args.vocab,
        max_len=args.seq_len, n_layer=args.n_layer,
        n_head=args.n_head, d_model=args.d_model,
        d_inner=args.d_inner, attention_impl="composed")


def _transformer_feed(args, rng):
    ids = rng.randint(1, args.vocab,
                      size=(args.batch, args.seq_len, 1)).astype(np.int64)
    return {
        "src_ids": ids, "trg_ids": ids, "trg_labels": ids,
        "pos_ids": np.arange(args.seq_len, dtype=np.int64),
    }, args.batch * args.seq_len


def _lstm_build(args):
    from paddle_tpu.models import lstm_lm
    return lambda: lstm_lm.build_train(
        vocab_size=args.vocab, emb_dim=args.d_model // 2,
        hid_dim=args.d_model, num_layers=args.n_layer)


def _lstm_feed(args, rng):
    from paddle_tpu.core.lod import LoDTensor
    per_row = args.seq_len
    total = args.batch * per_row
    data = rng.randint(1, args.vocab, size=(total, 1)).astype(np.int64)
    lod = [[i * per_row for i in range(args.batch + 1)]]
    return {"words": LoDTensor(data, lod),
            "targets": LoDTensor(data, lod)}, total


def measure(build, feed, loss_name, args):
    """(tokens_per_sec, spread_pct, losses[3]) for the current arm."""
    import paddle_tpu as pt
    from bench import _marginal_steps_per_sec

    main, startup, fetches = build()
    loss = fetches[loss_name] if isinstance(fetches, dict) else fetches
    scope = pt.Scope()
    exe = pt.Executor()
    with pt.scope_guard(scope):
        exe.run(startup)
        sps, spread = _marginal_steps_per_sec(
            exe, main, feed, loss, n1=args.skip_batch_num,
            n2=args.iterations, repeats=args.repeats)
        losses = [float(np.ravel(np.asarray(
            exe.run(main, feed=feed, fetch_list=[loss])[0]))[0])
            for _ in range(3)]
    return sps, 100.0 * spread, losses


def rewrite_overhead(build, feeds, fetch_names):
    """Offline pipeline wall time + action summary for one model."""
    from paddle_tpu.analysis import rewrite
    main, _startup, fetches = build()
    if isinstance(fetches, dict):
        fetch_names = [v.name for v in fetches.values()]
    res = rewrite.rewrite_program(main, feed_names=feeds,
                                  fetch_names=fetch_names)
    return res.summary()


def network_sweep():
    """DCE/CSE over the 9 lint_ir networks under the training
    (loss-only) fetch stance; truthful per-network counts."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from lint_ir import NETWORKS, optimize_report
    out = {}
    for name in sorted(NETWORKS):
        s = optimize_report(network=name, train_fetch=True)
        out[name] = {"ops_removed": s["ops_removed"],
                     "outlined": s["outlined"],
                     "passes": s["passes"]}
    out["networks_with_dce_cse"] = sum(
        1 for v in out.values()
        if isinstance(v, dict) and v.get("ops_removed", 0) > 0)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--vocab", type=int, default=4000)
    ap.add_argument("--n-layer", type=int, default=2)
    ap.add_argument("--n-head", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--d-inner", type=int, default=2048)
    ap.add_argument("--iterations", type=int, default=20)
    ap.add_argument("--skip_batch_num", type=int, default=5)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--models", default="transformer,lstm_lm")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + 1 repeat: protocol/CI check, "
                         "not a perf number")
    ap.add_argument("--no-sweep", action="store_true",
                    help="skip the 9-network DCE/CSE sweep")
    ap.add_argument("--json", help="write the report here (default "
                                   "stdout only)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.batch, args.seq_len, args.vocab = 2, 16, 64
        args.n_layer, args.n_head = 1, 2
        args.d_model, args.d_inner = 32, 64
        args.iterations, args.skip_batch_num, args.repeats = 4, 1, 1

    rng = np.random.RandomState(0)
    specs = {
        "transformer": (_transformer_build(args),
                        _transformer_feed(args, rng),
                        ["src_ids", "trg_ids", "trg_labels", "pos_ids"],
                        "loss"),
        "lstm_lm": (_lstm_build(args), _lstm_feed(args, rng),
                    ["words", "targets"], "loss"),
    }
    report = {"config": {k: getattr(args, k) for k in
                         ("batch", "seq_len", "vocab", "n_layer",
                          "n_head", "d_model", "d_inner", "iterations",
                          "repeats", "smoke")},
              "models": {}}
    for name in args.models.split(","):
        build, (feed, tokens_per_step), feed_names, loss_key = \
            specs[name.strip()]
        entry = {}
        for arm in ("off", "on"):
            _set_arm(arm)
            t0 = time.time()
            sps, spread, losses = measure(build, feed, loss_key, args)
            entry[arm] = {
                "steps_per_sec": round(sps, 4),
                "tokens_per_sec": round(sps * tokens_per_step, 1),
                "spread_pct": round(spread, 1),
                "losses_3steps": losses,
                "wall_s": round(time.time() - t0, 1),
            }
        _set_arm("on")
        entry["speedup"] = round(
            entry["on"]["tokens_per_sec"]
            / max(entry["off"]["tokens_per_sec"], 1e-9), 3)
        entry["loss_max_abs_diff"] = max(
            abs(a - b) for a, b in zip(entry["off"]["losses_3steps"],
                                       entry["on"]["losses_3steps"]))
        entry["rewrite"] = rewrite_overhead(build, feed_names, None)
        report["models"][name.strip()] = entry
        print(f"{name:12s} off {entry['off']['tokens_per_sec']:>12,.0f} "
              f"tok/s (spread {entry['off']['spread_pct']:.0f}%)  "
              f"on {entry['on']['tokens_per_sec']:>12,.0f} tok/s "
              f"(spread {entry['on']['spread_pct']:.0f}%)  "
              f"speedup {entry['speedup']}x  "
              f"rewrite {entry['rewrite']['seconds'] * 1e3:.0f} ms",
              flush=True)
    _set_arm("on")
    for k in _KERNEL_KNOBS:
        os.environ.pop(k, None)
    os.environ.pop("PADDLE_TPU_OPTIMIZE", None)
    if not args.no_sweep:
        report["network_sweep"] = network_sweep()
        n = report["network_sweep"]["networks_with_dce_cse"]
        print(f"network sweep: {n}/9 lint networks with nonzero "
              f"DCE/CSE ops removed (loss-only training fetch; the "
              f"rest are already minimal graphs)")
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
