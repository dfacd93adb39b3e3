"""Mean per step of the host's own serial time in the trainer loop:
the window's wall less the spans in which the loop only waits —
``pipeline::fetch_sync`` (with a loss fetched every step that span IS
the wait for the device) and the two input waits. What is left is
``pipeline::dispatch`` plus the loop's Python between spans, during
which the device has nothing queued."""


def read(run):
    if run.get("kind") != "train" or not run.get("steps"):
        return None
    t0, t1 = run["window"]
    waiting = sum(s.dur for name in ("pipeline::fetch_sync",
                                     "pipeline::prefetch_wait",
                                     "pipeline::host_blocked")
                  for s in run["spans"].named(name, t0, t1))
    return max(0.0, (t1 - t0) - waiting) / len(run["steps"]) * 1e3
