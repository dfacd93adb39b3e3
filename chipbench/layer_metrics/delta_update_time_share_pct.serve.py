"""Time of the delta-rule state update kernels over device busy time on
device 0 in the traced window. The program names the ``pallas_call`` of
a decode step's matrix-state update ``delta_state_update``
(ops/pallas/delta_state_update.py), XLA names each custom call's
instruction after its kernel, and the loader's ``<instruction>
custom-call:tpu_custom_call`` carries it: one call a linear-attention
layer a decode step. None without a device plane (a rehearsal) and on a
program with no such kernel (every other configuration, the parent)."""
import re

DELTA_UPDATE = re.compile(
    r"^delta_state_update\S* custom-call:tpu_custom_call$")


def read(run):
    from chipbench.program_spans import kernel_share_pct
    return kernel_share_pct(run, DELTA_UPDATE)
