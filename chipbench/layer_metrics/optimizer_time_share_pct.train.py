"""Device time of the instructions that the optimizer's update ops
emitted (role ``optimizer``: ``adam``, ``sgd``, ``momentum`` and the
other rules the cost model lists), over device busy time on device 0 in
the traced window; see ``chipbench/program_ops.py``. None without a
device plane (a rehearsal), on a run that is not a train run and on a
program that keeps no op table."""


def read(run):
    from chipbench.program_ops import role_share_pct
    return role_share_pct(run, "optimizer")
