"""Time of the forward flash-attention kernel over device busy time on
device 0 in the traced window. The program names its three
``pallas_call``s (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``),
and XLA names each custom call's instruction after its kernel
(``jvp_flash_fwd_.1``, PERF.md, PR 25): the loader's
``<instruction> custom-call:tpu_custom_call`` carries it. None without
a device plane (a rehearsal) and on a program whose kernels have no
name."""
import re

KERNEL = re.compile(r"flash_fwd\S* custom-call:tpu_custom_call$")


def read(run):
    from chipbench.program_spans import kernel_share_pct
    return kernel_share_pct(run, KERNEL)
