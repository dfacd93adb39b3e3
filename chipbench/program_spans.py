"""What the readers of the program's own spans share. The spans are
the ``profiler.RecordEvent``s the Collector heard (chipbench/spans.py):
``compile::*`` around a first dispatch, ``pipeline::prepare|commit``
around every dispatch, ``trainer::*`` and ``generation::*`` in the two
loops. A program without them (the parent of the PR that added them)
gives every reader nothing to read, and it returns None.

Every reader here also returns None when ``run["reduced"]`` is None —
no device plane, i.e. a rehearsal off the chip: a host time read on a
CPU is not reported under the name of a chip run's metric."""
from __future__ import annotations

from chipbench.trace import union


def on_chip(run) -> bool:
    return run.get("reduced") is not None


def union_seconds(spans) -> float:
    """Seconds covered by the spans together: an inner jit fires its
    own compile event inside an outer one's, so a sum counts twice."""
    return sum(e - s for s, e in union((sp.start, sp.end) for sp in spans))


def setup_seconds(run, names):
    """Union of the named spans that ended before the window opened,
    or None."""
    if not on_chip(run):
        return None
    t0 = run["window"][0]
    spans = [s for n in names for s in run["spans"].named(n, None, t0)]
    return union_seconds(spans) if spans else None


def in_window(run, names) -> list:
    t0, t1 = run["window"]
    return [s for n in names for s in run["spans"].named(n, t0, t1)]


def ms_per_step(run, names):
    """Mean per measured train step of the named spans' summed time in
    the window, or None."""
    if not on_chip(run) or run.get("kind") != "train" \
            or not run.get("steps"):
        return None
    spans = in_window(run, names)
    if not spans:
        return None
    return sum(s.dur for s in spans) / len(run["steps"]) * 1e3


def p95_ms(values):
    import numpy as np
    return float(np.percentile(values, 95)) * 1e3 if values else None


def kernel_share_pct(run, pattern):
    """Time of the device operations whose short name matches, over
    device busy time, device 0 of the traced window; None where nothing
    matches (a program whose kernels have no name)."""
    red = run.get("reduced")
    if red is None:
        return None
    seconds, busy = red.seconds(pattern, 0), red.busy_on(0)
    return seconds / busy * 100.0 if seconds and busy else None
