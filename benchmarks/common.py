"""Shared protocol for the model benchmarks (mirrors the reference's
benchmark/fluid/run.sh contract: --batch_size / --iterations /
--skip_batch_num, then report average throughput).

Timing uses the marginal-cost method from bench.py (see its module
docstring)."""
from __future__ import annotations

import argparse
import os
import sys

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


def run_benchmark(exe, program, feed, loss_var, args, unit_per_step,
                  unit="samples"):
    """Warm up, then marginal-cost time (iterations - skip_batch_num
    extra steps) via bench.py's shared helper; print the
    reference-style summary line."""
    from bench import _marginal_steps_per_sec
    steps_per_sec = _marginal_steps_per_sec(
        exe, program, feed, loss_var,
        n1=args.skip_batch_num, n2=args.iterations)
    (loss,) = exe.run(program, feed=feed, fetch_list=[loss_var],
                      return_numpy=False)
    last_loss = float(np.ravel(np.asarray(loss))[0])
    per_sec = unit_per_step * steps_per_sec
    print(f"last loss: {last_loss:.4f}")
    print(f"throughput: {per_sec:,.1f} {unit}/sec "
          f"({1.0 / steps_per_sec * 1e3:.1f} ms/batch)")
    return per_sec


def time_chain(fn, x0, flops_per_call, label, n1=10, n2=110,
               repeats=3, peak_flops=None):
    """Kernel-A/B marginal timing: jit with donated self-chained arg,
    3 warmups + a synced throwaway, then median of `repeats` marginal
    deltas t(n2)-t(n1). Shared by the kernel A/B harnesses so protocol
    fixes land once."""
    import time

    import jax
    import jax.numpy as jnp

    if peak_flops is None:  # the attached device's peak, or an error
        from paddle_tpu.observability.attribution import \
            require_peak_flops
        peak_flops = require_peak_flops()

    jitted = jax.jit(fn, donate_argnums=(0,))
    x = jnp.copy(x0)

    def run_n(x, n):
        t0 = time.perf_counter()
        for _ in range(n):
            x = jitted(x)
        s = float(np.asarray(jnp.sum(
            jnp.ravel(x)[:1].astype(jnp.float32))))
        assert np.isfinite(s), label
        return x, time.perf_counter() - t0

    for _ in range(3):
        x = jitted(x)
    x, _ = run_n(x, 1)
    ests = []
    for _ in range(repeats):
        x, t1 = run_n(x, n1)
        x, t2 = run_n(x, n2)
        ests.append((t2 - t1) / (n2 - n1))
    dt = float(np.median(ests))
    spread = (max(ests) - min(ests)) / dt
    tflops = flops_per_call / dt / 1e12
    print(f"{label:26s} {dt * 1e3:8.2f} ms/call  {tflops:6.1f} TFLOP/s"
          f" ({100 * tflops * 1e12 / peak_flops:4.1f}% of peak)  "
          f"spread {100 * spread:.0f}%", flush=True)
    return dt
