"""Seconds, before the window, inside the program's own analyses of a
first dispatch: the union of the ``compile::verify`` (verifier gate),
``compile::rewrite``, ``compile::memory_plan`` and
``compile::cost_model`` spans. None without a device plane (a
rehearsal) and on a program without these spans."""


def read(run):
    from chipbench.program_spans import setup_seconds
    return setup_seconds(run, ("compile::verify", "compile::rewrite",
                               "compile::memory_plan",
                               "compile::cost_model"))
