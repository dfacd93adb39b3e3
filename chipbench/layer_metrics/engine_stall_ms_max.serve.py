"""The longest ``generation::stall`` span that ended in the window, 0
when there is none: a stretch of passes of the engine's loop (at most
50 ms of them and the one that paused) whose thread neither ran nor
waited for the device for more than 50 ms (the span's args say
whether the process got no CPU, another thread burned it, or the thread
was pre-empted). It says whether THIS run held a pause: on a pair of
runs that disagree it says which side did. None without a device plane
(a rehearsal), on a run that is not a serve run and on a program without
the pass's spans (no ``generation::collect``)."""


def read(run):
    from chipbench.engine_pass import has_pass_spans, spans
    if not has_pass_spans(run):
        return None
    stalls = spans(run, "generation::stall")
    return max((s.dur for s in stalls), default=0.0) * 1e3
