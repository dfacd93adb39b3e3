"""Device time of the instructions that grad ops emitted (role
``backward``: op types ``__vjp__.<forward type>``, the backward flash
kernel among them, since a Mosaic call counts under the op whose rule
emitted it), over device busy time on device 0 in the traced window;
see ``chipbench/program_ops.py``. None without a device plane (a
rehearsal), on a run that is not a train run and on a program that
keeps no op table."""


def read(run):
    from chipbench.program_ops import role_share_pct
    return role_share_pct(run, "backward")
