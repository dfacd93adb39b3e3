"""Device time of the instructions that the program's op table maps to
an op of the ``Program`` (any role), over device busy time on device 0
in the traced window: how much of the device's time the other three
readers of ``chipbench/program_ops.py`` can see at all. The rest is
XLA's own (``copy``, ``copy-start`` / ``copy-done``, ``ConcatBitcast``):
instructions whose metadata carries no ``op_name``. None without a
device plane (a rehearsal), on a run that is not a train run and on a
program that keeps no op table."""


def read(run):
    from chipbench.program_ops import share_pct
    return share_pct(run, lambda ref: True)
