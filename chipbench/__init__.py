"""chipbench — the repo's yardstick: cells, traffic, trace reduction,
arithmetic and references, all found by name from BENCHMARK.json.

Nothing here is imported by paddle_tpu; the benchmark takes from the
program only the system under test, its RecordEvent spans, its counters
and its kernel names. See chipbench/README.md for how to add a cell, a
configuration or a per-layer metric as files.
"""
