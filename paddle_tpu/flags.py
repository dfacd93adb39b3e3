"""Central registry of the framework's environment flags.

The reference wires gflags end-to-end and re-exports selected C++ flags
into Python via `core.init_gflags(["--tryfromenv=..."])` at import
(reference: python/paddle/fluid/__init__.py:76-111 — use_pinned_memory,
check_nan_inf, benchmark, fraction_of_gpu_memory_to_use, ...). The
TPU-native analog is plain environment variables read at trace/run time;
this module is the single place they are all documented and inspectable
(`paddle_tpu.flags.dump()`), replacing the reference's --help surface.
"""
from __future__ import annotations

import os
from typing import Dict

# name -> (default, where it is read, what it does)
FLAGS: Dict[str, tuple] = {
    "PADDLE_TPU_AMP": (
        "0", "amp.py",
        "bf16 mixed precision (f32 master weights)"),
    "PADDLE_TPU_CHECK_NAN_INF": (
        "0", "core/executor.py",
        "scan fetched values for NaN/Inf after each run (reference "
        "FLAGS_check_nan_inf)"),
    "PADDLE_TPU_DONATE_STATE": (
        "1", "core/executor.py",
        "donate rw persistable state to the jitted step (XLA aliases "
        "state-in to state-out in place of a copy per step); 0 restores "
        "copy-per-step for callers holding scope state across runs"),
    "PADDLE_TPU_PALLAS_LSTM": (
        "1", "ops/sequence_ops.py",
        "fused Pallas LSTM kernel on TPU ('force' = interpret mode "
        "anywhere for tests, '0' = scan path)"),
    "PADDLE_TPU_PALLAS_GRU": (
        "1", "ops/sequence_ops.py",
        "fused Pallas GRU kernel on TPU (same force/0/1 semantics)"),
    "PADDLE_TPU_CHECK_WHILE_BOUND": (
        "0", "core/executor.py",
        "raise when a top-level bounded While (max_steps=N) truncated a "
        "loop whose condition was still true; default 0 warns once per "
        "flag instead (per-run host readback; the `<name>.exhausted` "
        "bool var is always available to fetch; loops nested in "
        "sub-blocks keep their flag block-local)"),
    "PADDLE_TPU_VERIFY": (
        "1", "analysis/verifier.py (gates in core/executor.py, "
        "serving/model.py, trainer.py, io.py)",
        "static program verification gates: pre-compile (executor "
        "cache miss), serving model load, trainer setup, and "
        "save_inference_model all raise VerificationError on "
        "error-severity diagnostics; 0 disables every gate (the "
        "executor trace remains the runtime authority)"),
    "PADDLE_TPU_DATA_HOME": (
        "~/.cache/paddle_tpu/dataset", "dataset/common.py",
        "dataset download/cache directory"),
    "PADDLE_TPU_FEED_CACHE_MAX": (
        "8", "core/executor.py",
        "max entries in the device-side feed cache (frozen ndarrays "
        "uploaded once)"),
    "PADDLE_TPU_ATTRIBUTION": (
        "1", "observability/attribution.py (published from trainer.py, "
        "serving/engine.py)",
        "live performance attribution: paddle_tpu_mfu / "
        "paddle_tpu_model_flops gauges and the per-phase step-time "
        "breakdown; 0 disables publication (the disabled metrics "
        "registry also turns it off; set_attribution_enabled() "
        "overrides the env)"),
    "PADDLE_TPU_PEAK_FLOPS": (
        "", "observability/attribution.py",
        "device peak FLOP/s the MFU gauge is normalized against; "
        "unset, the peak comes from attribution."
        "PEAK_FLOPS_BY_DEVICE_KIND keyed by the device JAX reports, "
        "and a device not in that table publishes no paddle_tpu_mfu "
        "(benchmarks raise). Read per step so tests can flip it"),
    "PADDLE_TPU_FLIGHT_RECORDER": (
        "1", "observability/flight_recorder.py",
        "failure flight recorder: bounded ring of recent profiler "
        "events dumped as a chrome-trace + JSON bundle when a failure "
        "trigger fires (NaN at fetch, circuit-breaker open, checkpoint "
        "failure, VerificationError); 0 removes the listener entirely "
        "(zero overhead, nothing ever written)"),
    "PADDLE_TPU_FLIGHT_DIR": (
        "<tmpdir>/paddle_tpu_flightrec", "observability/flight_recorder.py",
        "directory flight-recorder dump bundles are written to "
        "(flightrec_<ms>_<pid>_<seq>_<reason>/, pruned to this "
        "process's newest 8)"),
    "PADDLE_TPU_HBM_BYTES": (
        str(16 * 1024 ** 3), "analysis/memory.py (gate in "
        "core/executor.py)",
        "per-core HBM budget for the pre-compile OOM gate: a program "
        "whose static free-at-last-use peak (MemoryReport."
        "ideal_peak_bytes) exceeds this raises a structured "
        "VerificationError (top offenders + high-water op) before XLA "
        "compiles it. Default one v5e core (16 GiB); "
        "0 disables the gate (the MemoryReport is still attached)"),
    "PADDLE_TPU_PALLAS_SDPA": (
        "1", "ops/nn_ops.py (scaled_dot_product_attention rule)",
        "flash-kernel choice for scaled_dot_product_attention ops "
        "that carry no use_flash attr of their own, read when the op "
        "(and its grad op) is traced: '1' leaves the measured min-seq "
        "policy in charge on a TPU (ops/pallas/flash_attention.py "
        "FLASH_CROSSOVER_SEQ: q and k of 256 or more since PR 51, a "
        "site of 256-511 under the kernels' short-sequence plan; a "
        "shorter one is composed), "
        "'force' engages the kernel anywhere (interpret mode off-TPU "
        "— test coverage), '0' pins the naive composition"),
    "PADDLE_TPU_INPUT_WORKERS": (
        "2", "reader/streaming.py",
        "initial worker-process count of a StreamingInputService "
        "(capped at the shard count; elastic scaling moves it between "
        "MIN and MAX at runtime)"),
    "PADDLE_TPU_INPUT_MIN_WORKERS": (
        "1", "reader/streaming.py",
        "elastic-scaling floor for the streaming input worker pool"),
    "PADDLE_TPU_INPUT_MAX_WORKERS": (
        "4", "reader/streaming.py",
        "elastic-scaling ceiling for the streaming input worker pool "
        "(also capped at the shard count — a shard is the unit of "
        "parallelism)"),
    "PADDLE_TPU_INPUT_SLOTS": (
        "4", "reader/streaming.py",
        "shared-memory ring slots per streaming input worker; bounds "
        "each worker's produced-but-undelivered batches (backpressure) "
        "and so the service's reorder-buffer memory"),
    "PADDLE_TPU_INPUT_SCALE_INTERVAL_S": (
        "2.0", "reader/streaming.py",
        "elastic-scaling evaluation window: starvation above "
        "PADDLE_TPU_INPUT_SCALE_UP_STARVED spawns a worker, a full "
        "queue with zero starvation retires one; 0 disables scaling"),
    "PADDLE_TPU_INPUT_SCALE_UP_STARVED": (
        "0.25", "reader/streaming.py",
        "fraction of deliveries in a scaling window that found the "
        "prefetch queue dry above which the pool scales up"),
    "PADDLE_TPU_INPUT_START_METHOD": (
        "spawn", "reader/streaming.py",
        "multiprocessing start method for streaming input workers "
        "('spawn' default — fork duplicates live JAX runtime threads; "
        "chaos tests use 'fork' so workers inherit the armed "
        "FaultInjector)"),
    "PADDLE_TPU_INPUT_MAX_RESPAWNS": (
        "3", "reader/streaming.py",
        "total worker respawns a StreamingInputService attempts across "
        "its lifetime before surfacing the crash to the consumer"),
    "PADDLE_TPU_DECODE_SLOTS": (
        "4", "serving/generation/model.py",
        "default in-flight slot count of a generation model's "
        "continuous-batching array (per-request KV-cache rows; also "
        "the decode executable's batch dimension)"),
    "PADDLE_TPU_DECODE_CACHE_BUCKETS": (
        "16,32,64", "serving/generation/model.py",
        "default cache-length buckets for the decode-step executables, "
        "comma-separated ascending; each bucket is one compiled "
        "executable, a step runs the smallest bucket covering the "
        "deepest active position"),
    "PADDLE_TPU_DECODE_MODEL_BUDGET": (
        "8", "serving/generation/host.py",
        "default per-model admission budget of a GenerationHost: max "
        "concurrently admitted (queued + in-flight) requests per "
        "hosted model before sheds with reason=model_budget"),
    "PADDLE_TPU_EMBED_HOT_CACHE_ROWS": (
        "1024", "embedding/hot_cache.py (via embedding/table.py)",
        "default row capacity of a ShardedTable's replicated hot-row "
        "cache (top-K by observed frequency); 0 disables the cache so "
        "every id takes the cold sharded-gather path"),
    "PADDLE_TPU_EMBED_CACHE_REFRESH_STEPS": (
        "50", "embedding/hot_cache.py",
        "steps between hot-cache refreshes: the host-side frequency "
        "tracker re-elects the top-K rows and re-gathers their current "
        "values; also the cache's staleness bound — between refreshes "
        "only write-through updates (rows this worker touched) land "
        "in the cache"),
    "PADDLE_TPU_EMBED_FREQ_CAPACITY": (
        "8192", "embedding/hot_cache.py",
        "bounded id-frequency tracker capacity (lossy top-K counting "
        "— a dense per-row counter would be O(vocab) host memory, "
        "unpayable at 1e9 rows); pruned back to this size whenever it "
        "doubles"),
}


def get(name: str) -> str:
    """Current value of a registered flag (env or default)."""
    if name not in FLAGS:
        raise KeyError(f"unknown flag {name!r}; see paddle_tpu.flags.FLAGS")
    return os.environ.get(name, FLAGS[name][0])


def dump() -> str:
    """Human-readable table of every flag: current value, default,
    reader, description."""
    lines = []
    for name, (default, where, desc) in sorted(FLAGS.items()):
        cur = os.environ.get(name)
        mark = f"{cur} (set)" if cur is not None else f"{default}"
        lines.append(f"{name} = {mark}\n    [{where}] {desc}")
    return "\n".join(lines)
