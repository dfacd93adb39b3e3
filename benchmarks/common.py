"""Shared protocol for the model benchmarks (mirrors the reference's
benchmark/fluid/run.sh contract: --batch_size / --iterations /
--skip_batch_num, then report average throughput).

Timing is MARGINAL-COST: run N1 and N2 iterations, each fully synced by a
host readback of the final loss (step i+1 consumes step i's donated state,
so the readback drains the whole chain), and divide the extra work by the
extra time. This cancels the fixed cost of a window (first dispatch, the
final readback) that would otherwise be billed to the steps. These scripts
are the parity surface of PARITY.md; the numbers the repo stands behind
come from BENCHMARK.json + chipbench/."""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

# runnable from anywhere: repo root on path (reference scripts assume the
# package is installed; this repo is used in-tree)
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def parse_args(extra=None):
    p = argparse.ArgumentParser()
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--iterations", type=int, default=25,
                   help="minibatches in the long timing run")
    p.add_argument("--skip_batch_num", type=int, default=5,
                   help="warmup minibatches (and the short timing run)")
    p.add_argument("--no_amp", action="store_true",
                   help="disable bf16 mixed precision")
    for name, kw in (extra or {}).items():
        p.add_argument(name, **kw)
    args = p.parse_args()
    if args.iterations <= args.skip_batch_num:
        p.error("--iterations must exceed --skip_batch_num")
    return args


def _marginal_steps_per_sec(exe, program, feed, loss_var, n1, n2):
    """Marginal steps/sec via two synced runs of different lengths: the
    (n1, n2) pair is measured twice; returns the MEDIAN estimate and
    the relative spread (max-min over median)."""
    def one_step():
        (out,) = exe.run(program, feed=feed, fetch_list=[loss_var],
                         return_numpy=False)
        return out

    def timed(n):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = one_step()
        val = np.asarray(out)  # host readback drains the step chain
        if not np.isfinite(np.ravel(val)[0]):
            raise RuntimeError("non-finite loss in bench — result invalid")
        return time.perf_counter() - t0

    for _ in range(3):
        one_step()   # the frozen feed is uploaded (and cached) here
    timed(1)  # synced throwaway: drains lazy compiles
    ests = []
    for _ in range(2):
        t1 = timed(n1)
        t2 = timed(n2)
        if t2 <= t1:
            raise RuntimeError(
                f"marginal timing invalid: t({n2})={t2:.3f}s <= "
                f"t({n1})={t1:.3f}s — timing not steady-state")
        ests.append((n2 - n1) / (t2 - t1))
    med = float(np.median(ests))
    return med, (max(ests) - min(ests)) / med


def run_benchmark(exe, program, feed, loss_var, args, unit_per_step,
                  unit="samples"):
    """Warm up, then marginal-cost time (iterations - skip_batch_num
    extra steps); print the reference-style summary line."""
    steps_per_sec, spread = _marginal_steps_per_sec(
        exe, program, feed, loss_var,
        n1=args.skip_batch_num, n2=args.iterations)
    (loss,) = exe.run(program, feed=feed, fetch_list=[loss_var],
                      return_numpy=False)
    last_loss = float(np.ravel(np.asarray(loss))[0])
    per_sec = unit_per_step * steps_per_sec
    print(f"last loss: {last_loss:.4f}")
    print(f"throughput: {per_sec:,.1f} {unit}/sec "
          f"({1.0 / steps_per_sec * 1e3:.1f} ms/batch, "
          f"spread {100 * spread:.0f}%)")
    return per_sec
