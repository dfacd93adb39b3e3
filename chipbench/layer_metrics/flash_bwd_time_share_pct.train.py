"""Time of the two backward flash-attention kernels (``flash_bwd_dq``
and ``flash_bwd_dkv``) over device busy time on device 0 in the traced
window; see ``flash_fwd_time_share_pct.train``. None without a device
plane (a rehearsal) and on a program whose kernels have no name."""
import re

KERNEL = re.compile(r"flash_bwd_(dq|dkv)\S* custom-call:tpu_custom_call$")


def read(run):
    from chipbench.program_spans import kernel_share_pct
    return kernel_share_pct(run, KERNEL)
