"""Reduction of a profiler trace to numbers: device busy and idle,
kernel and collective time, the top operations and what the host was
doing in the longest idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``load_xplane`` reads it with nothing but JAX into the plain form the
rest of this file (and the recorded fixture under tests/chipbench)
works on:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

All reductions are pure functions of that form, so a test can check
them on a small recorded trace without a chip.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# The line of a device plane that holds one event per executed HLO
# operation. "XLA Modules"/"Steps" hold whole-program spans and would
# make the device look busy between operations.
OPS_LINE = "XLA Ops"
MARKER = "chipbench::window_start"

# An operation's event carries its whole HLO line
# (``%step_fn.24 = (bf16[8,8,2048,64]{...}, ...) custom-call(...),
# custom_call_target="tpu_custom_call", ...``); ``short_name`` keeps
# ``<name> <opcode>[:<custom-call target>]`` and the patterns below read
# that. The program gives its kernels no ``name=``, so a Mosaic (Pallas)
# kernel is known only by its target; XLA's own custom calls
# (``ConcatBitcast``) are not kernels.
COLLECTIVE = re.compile(
    r" (all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(-start|-done)?$")
CUSTOM_CALL = re.compile(r" custom-call:tpu_custom_call$")
_HLO_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_HLO_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(hlo: str) -> str:
    """``<name> <opcode>[:<target>]`` of an HLO line; anything that is
    not one is returned as it is."""
    name, eq, rest = hlo.partition(" = ")
    if not eq:
        return hlo
    op = _HLO_OPCODE.search(rest)
    out = name.lstrip("%") + " " + (op.group(1) if op else "?")
    if op and op.group(1) == "custom-call":
        target = _HLO_TARGET.search(rest)
        out += ":" + (target.group(1) if target else "?")
    return out


# -- loading ----------------------------------------------------------

def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load_xplane(path: str, all_lines: bool = False) -> dict:
    """Plain form of an .xplane.pb. Unless ``all_lines``, only what the
    reductions read is kept: each device plane's operations line, and
    of the host's planes just the window marker (a host line can hold
    millions of events)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if on_device and not all_lines and line.name != OPS_LINE:
                continue
            events = [[short_name(e.name) if on_device else e.name,
                       float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if on_device or all_lines or e.name == MARKER]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def outline(path: str, samples: int = 3) -> list:
    """Planes, lines, event counts and a few event names of an
    .xplane.pb: look at one trace by hand before writing code against
    it (``python3 -m chipbench.trace <file.xplane.pb>``)."""
    plain = load_xplane(path, all_lines=True)
    return [{"plane": p["name"], "lines": [
        {"line": ln["name"], "events": len(ln["events"]),
         "first": [e[0] for e in ln["events"][:samples]]}
        for ln in p["lines"]]} for p in plain["planes"]]


def save_plain(trace: dict, path: str):
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load_plain(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# -- selection ----------------------------------------------------------

def device_ids(trace: dict) -> list:
    out = []
    for p in trace["planes"]:
        m = DEVICE_PLANE.match(p["name"])
        if m:
            out.append(int(m.group(1)))
    return sorted(out)


def device_ops(trace: dict, device: int = 0, t0=None, t1=None) -> list:
    """[name, start_ns, dur_ns] of every operation on one device,
    clipped to [t0, t1] (trace clock, ns), sorted by start."""
    want = f"/device:TPU:{device}"
    evs = []
    for p in trace["planes"]:
        if p["name"] != want:
            continue
        for line in p["lines"]:
            if line["name"] == OPS_LINE:
                evs.extend(line["events"])
    out = []
    for name, s, d in evs:
        e = s + d
        if t0 is not None:
            s = max(s, t0)
        if t1 is not None:
            e = min(e, t1)
        if e > s:
            out.append([name, s, e - s])
    out.sort(key=lambda x: x[1])
    return out


def marker_ns(trace: dict, name: str = MARKER):
    """Start (trace clock, ns) of the first host event called ``name``:
    the benchmark emits one beside a ``perf_counter`` reading, which
    puts host spans on the trace's clock."""
    best = None
    for p in trace["planes"]:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for line in p["lines"]:
            for n, s, _d in line["events"]:
                if n == name and (best is None or s < best):
                    best = s
    return best


# -- reductions -----------------------------------------------------------

def union(intervals) -> list:
    """Merged [start, end] intervals of (start, end) pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def busy_ns(ops: list) -> float:
    return sum(e - s for s, e in union((s, s + d) for _n, s, d in ops))


def idle_gaps(ops: list, t0: float, t1: float) -> list:
    """(start, end) of every interval of [t0, t1] in which no operation
    ran."""
    gaps, cur = [], t0
    for s, e in union((s, s + d) for _n, s, d in ops):
        if s > cur:
            gaps.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        gaps.append((cur, t1))
    return [(a, b) for a, b in gaps if b > a]


def total_ns(ops: list, pattern) -> float:
    """Summed duration of the operations whose name matches."""
    return sum(d for n, _s, d in ops if pattern.search(n))


def top_ops(ops: list, k: int = 10) -> list:
    """[[name, seconds], ...] of the operations that took most time,
    occurrences of one name summed. The Mosaic kernels (one unnamed
    custom call each) and each kind of collective go under one entry."""
    agg = {}
    for n, _s, d in ops:
        if CUSTOM_CALL.search(n):
            n = "tpu_custom_call (all Mosaic kernels)"
        elif COLLECTIVE.search(n):
            n = n.rsplit(" ", 1)[1] + " (all)"
        agg[n] = agg.get(n, 0.0) + d
    best = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d * 1e-9] for n, d in best]


# Gaps are attributed one by one only down to this many; the rest (the
# sub-microsecond seams between operations) are lumped together.
MAX_GAPS = 2000


def attribute_gaps(gaps: list, host_spans: list, k: int = 10) -> list:
    """[[what, seconds], ...]: idle time by what the host was doing.
    ``host_spans`` are (name, start_ns, end_ns) on the trace clock. A
    gap is charged to the innermost span over it: of the spans that
    cover at least half the gap the shortest one, else the one that
    overlaps it longest; what no span covers goes to "host:untracked".
    Only the MAX_GAPS longest gaps are looked at singly."""
    import numpy as np
    agg = {}

    def add(name, ns):
        if ns > 0:
            agg[name] = agg.get(name, 0.0) + ns

    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    add("short gaps between operations",
        sum(b - a for a, b in gaps[MAX_GAPS:]))
    names = [n for n, _s, _e in host_spans]
    starts = np.array([s for _n, s, _e in host_spans], np.float64)
    ends = np.array([e for _n, _s, e in host_spans], np.float64)
    for a, b in gaps[:MAX_GAPS]:
        if not names:
            add("host:untracked", b - a)
            continue
        ov = np.clip(np.minimum(b, ends) - np.maximum(a, starts), 0, None)
        cover = np.flatnonzero(ov >= 0.5 * (b - a))
        if len(cover):
            i = cover[np.argmin((ends - starts)[cover])]
        else:
            i = int(np.argmax(ov))
        add(names[i], float(ov[i]))
        add("host:untracked", (b - a) - float(ov[i]))
    best = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d * 1e-9] for n, d in best]


def span_family(name: str) -> str:
    """``generation::prefill[137]`` -> ``generation::prefill``,
    ``trace::step/12`` -> ``trace::step``: gaps are reported by kind of
    host work, not by prompt length or step number."""
    return re.split(r"[\[/]", name, maxsplit=1)[0]


class Reduced:
    """One traced window, reduced. Times in seconds."""

    def __init__(self, trace: dict, chips: int, window_ns=None,
                 host_spans=()):
        ids = device_ids(trace)[:chips]
        if not ids:
            raise ValueError("the trace holds no /device:TPU:<n> plane")
        all_ops = [device_ops(trace, i) for i in ids]
        if window_ns is None:
            starts = [o[0][1] for o in all_ops if o]
            ends = [max(s + d for _n, s, d in o) for o in all_ops if o]
            window_ns = (min(starts), max(ends)) if starts else (0.0, 0.0)
        self.t0, self.t1 = window_ns
        self.ops = [device_ops(trace, i, self.t0, self.t1) for i in ids]
        self.window_s = (self.t1 - self.t0) * 1e-9
        self.busy_s = sum(busy_ns(o) for o in self.ops) * 1e-9 \
            / len(self.ops)
        self.host_spans = [(span_family(n), s, e)
                           for n, s, e in host_spans]

    def seconds(self, pattern, device: int = 0) -> float:
        return total_ns(self.ops[device], pattern) * 1e-9

    def busy_on(self, device: int = 0) -> float:
        return busy_ns(self.ops[device]) * 1e-9

    def breakdown(self) -> dict:
        gaps = idle_gaps(self.ops[0], self.t0, self.t1)
        return {"device_ops": top_ops(self.ops[0]),
                "idle_gaps": attribute_gaps(gaps, self.host_spans)}


if __name__ == "__main__":
    import sys
    print(json.dumps(outline(sys.argv[1]), indent=1))
