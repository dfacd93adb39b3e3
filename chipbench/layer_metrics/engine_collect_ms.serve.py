"""Mean per ``generation::iteration`` span in the window of the
``generation::collect`` time before it: the head of a pass of the
engine's driver loop — taking the queue's lock (held by a submitting
client: waiting for it is in here by design), draining the queue, the
stop checks, the last pass into the loop's account. It is the part of a pass
that ``engine_host_ms.serve`` does not see, and was ``host:untracked``
between two iterations. None without a device plane (a rehearsal), on a
run that is not a serve run and on a program without the span."""


def read(run):
    from chipbench.engine_pass import spans
    collects = spans(run, "generation::collect")
    iterations = spans(run, "generation::iteration")
    if not collects or not iterations:
        return None
    return sum(s.dur for s in collects) / len(iterations) * 1e3
