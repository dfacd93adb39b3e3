"""Mean per ``generation::iteration`` span in the window (one pass of
the engine's driver loop: admissions, their prefills, one decode step,
delivery) of its duration less the ``pipeline::fetch_sync`` time inside
it — the waits for the device. What is left is the host's own work in
an iteration, dispatches included. None without a device plane (a
rehearsal) and on a program without the iteration span."""


def read(run):
    import numpy as np
    from chipbench.program_spans import in_window, on_chip
    if not on_chip(run) or run.get("kind") != "serve":
        return None
    iterations = sorted(in_window(run, ("generation::iteration",)),
                        key=lambda s: s.start)
    if not iterations:
        return None
    starts = np.array([s.start for s in iterations])
    total = sum(s.dur for s in iterations)
    for f in in_window(run, ("pipeline::fetch_sync",)):
        k = int(np.searchsorted(starts, f.start, side="right")) - 1
        if k >= 0 and f.end <= iterations[k].end:
            total -= f.dur
    return total / len(iterations) * 1e3
