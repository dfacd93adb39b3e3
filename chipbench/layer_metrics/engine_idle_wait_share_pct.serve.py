"""Summed ``generation::idle_wait`` spans that ended in the window over
the window: the share of it in which the engine had NOTHING to do — no
queued request, no live slot. Device idle time in it is the traffic's,
not the host's: about 0 at 0.8 of the knee, and a cell that reads high
is under-offered. 0 where the program has the pass's spans and never
idled; None without a device plane (a rehearsal), on a run that is not
a serve run and on a program without the spans (no
``generation::collect`` either)."""


def read(run):
    from chipbench.engine_pass import has_pass_spans, spans
    if not has_pass_spans(run):
        return None
    t0, t1 = run["window"]
    waited = sum(s.dur for s in spans(run, "generation::idle_wait"))
    return waited / (t1 - t0) * 100.0
