"""Median ``generation::decode_step[bucket]`` span in the window: one
decode program over the whole slot array, dispatch to fetched tokens."""


def read(run):
    import statistics
    if run.get("kind") != "serve":
        return None
    t0, t1 = run["window"]
    durs = [s.dur for s in
            run["spans"].named("generation::decode_step[", t0, t1)]
    return statistics.median(durs) * 1e3 if durs else None
