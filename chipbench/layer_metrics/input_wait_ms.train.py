"""Mean per step of the time the trainer loop waited for input:
``pipeline::prefetch_wait`` (consumer side of FeedPrefetcher) plus
``pipeline::host_blocked`` (inline feed assembly) spans in the window."""


def read(run):
    if run.get("kind") != "train" or not run.get("steps"):
        return None
    t0, t1 = run["window"]
    waited = sum(s.dur for name in ("pipeline::prefetch_wait",
                                    "pipeline::host_blocked")
                 for s in run["spans"].named(name, t0, t1))
    return waited / len(run["steps"]) * 1e3
