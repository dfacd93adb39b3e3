"""Mean per step of the executor's own host work around the jitted
call: ``pipeline::prepare`` (entry of ``Executor.run`` to the dispatch:
gate look-up, feed conversion, compile key, cache look-up, reading the
state arrays from the scope), ``pipeline::commit`` (the dispatch's
return to ``run``'s: the scope repointed at the new state, the
StepResult) and, across processes, ``pipeline::globalize_feed``. The
jitted call itself (``pipeline::dispatch``) is not in it. None without
a device plane (a rehearsal) and on a program without these spans."""


def read(run):
    from chipbench.program_spans import ms_per_step
    return ms_per_step(run, ("pipeline::prepare", "pipeline::commit",
                             "pipeline::globalize_feed"))
