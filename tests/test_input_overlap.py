"""Input-pipeline overlap proof without a device.

The double-buffering claim is proven here on the CPU backend with a
controlled slow loader + fake compute: total wall time must track
max(input, compute) per step, not their sum (reference:
operators/reader/create_double_buffer_reader_op.cc — the double-buffer
reader hides assembly latency behind compute).

Sleeps are coarse (40-80 ms) and the bounds generous so a loaded CI
machine cannot flake the assertion.
"""
import time

import numpy as np

from paddle_tpu import reader


def _timed_pipeline(t_in, t_c, n, buf_size=2):
    def slow_loader():
        for i in range(n):
            time.sleep(t_in)            # batch assembly (decode/collate)
            yield np.full((8,), i, np.float32)

    buffered = reader.double_buffer(slow_loader, size=buf_size)
    seen = []
    start = time.monotonic()
    for batch in buffered():
        time.sleep(t_c)                 # the compute step
        seen.append(batch[0])
    elapsed = time.monotonic() - start
    assert [int(s) for s in seen] == list(range(n))
    return elapsed


def test_double_buffer_hides_input_behind_compute():
    """Compute-bound: steady state should cost ~max = t_c per step; a
    serialized pipeline would cost t_in + t_c."""
    t_in, t_c, n = 0.04, 0.06, 10
    elapsed = _timed_pipeline(t_in, t_c, n)
    serial = n * (t_in + t_c)           # 1.00 s
    ideal = n * max(t_in, t_c) + t_in   # 0.64 s (one fill latency)
    assert elapsed < 0.82 * serial, (elapsed, serial)
    assert elapsed < ideal * 1.30, (elapsed, ideal)


def test_double_buffer_hides_compute_behind_input():
    """Input-bound: steady state should cost ~max = t_in per step."""
    t_in, t_c, n = 0.06, 0.03, 10
    elapsed = _timed_pipeline(t_in, t_c, n)
    serial = n * (t_in + t_c)           # 0.90 s
    ideal = n * max(t_in, t_c) + t_in   # 0.66 s
    assert elapsed < 0.87 * serial, (elapsed, serial)
    assert elapsed < ideal * 1.30, (elapsed, ideal)


def test_device_prefetch_preserves_order_and_readiness():
    """device_prefetch moves batches to the device on a producer thread
    and awaits readiness on the consumer thread; order and values are
    preserved (the correctness half of the overlap contract)."""
    n = 6

    def loader():
        for i in range(n):
            yield (np.full((4,), i, np.float32),
                   {"label": np.int32(i)})

    out = list(reader.device_prefetch(loader, size=2)())
    assert len(out) == n
    for i, (arr, d) in enumerate(out):
        np.testing.assert_allclose(np.asarray(arr), i)
        assert int(d["label"]) == i
