"""Summed time of the ``generation::prefill[n]`` spans that ended in the
window over the window: what serial prefill takes from decoding. The
engine runs one prefill at a time inside admission, and while one runs
no slot decodes, so this share of every second is added to the gaps
between the tokens of every request in flight. None without a device
plane (a rehearsal) and on a program without the span."""


def read(run):
    from chipbench.program_spans import in_window, on_chip
    if not on_chip(run) or run.get("kind") != "serve":
        return None
    spans = in_window(run, ("generation::prefill[",))
    if not spans:
        return None
    t0, t1 = run["window"]
    return sum(s.dur for s in spans) / (t1 - t0) * 100.0
