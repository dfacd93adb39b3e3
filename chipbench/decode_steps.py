"""What the readers of a serve run's DECODE STEPS share: the window's
``generation::decode_step[bucket]`` spans moved onto the trace's clock,
and the device operations that started inside them. A prefill's
operations carry the same names as a decode step's (the grouped
products, the appends), so a reader that means the steps alone takes the
operations by when they ran."""
from __future__ import annotations

import bisect


def intervals_by_bucket(run) -> dict:
    """{span name: sorted [(start, end)] in trace nanoseconds} of the
    decode-step spans that ended in the window, one entry a cache
    bucket (the bucket is in the span's name)."""
    red = run["reduced"]
    t0, t1 = run["window"]
    shift = red.t0 - t0 * 1e9
    out = {}
    for s in run["spans"].named("generation::decode_step[", t0, t1):
        out.setdefault(s.name, []).append(
            (s.start * 1e9 + shift, s.end * 1e9 + shift))
    return {name: sorted(spans) for name, spans in out.items()}


def inside(ops, intervals, pattern=None) -> list:
    """The operations [name, start, duration] that start inside one of
    the sorted intervals (and whose name matches)."""
    starts = [a for a, _b in intervals]
    out = []
    for n, s, d in ops:
        k = bisect.bisect_right(starts, s) - 1
        if k >= 0 and s < intervals[k][1] \
                and (pattern is None or pattern.search(n)):
            out.append((n, s, d))
    return out
