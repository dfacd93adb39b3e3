"""Time of the WINDOWED flash-attention kernels, forward and backward,
over device busy time on device 0 in the traced window. The program
names a windowed site's two ``pallas_call``s ``flash_fwd_window`` and
``flash_bwd_dkv_dq_window`` (ops/pallas/flash_attention.py), XLA names
each custom call's instruction after its kernel, and the loader's
``<instruction> custom-call:tpu_custom_call`` carries it;
``flash_fwd_time_share_pct.train`` and ``flash_bwd_time_share_pct.train``
count the same calls among all flash calls. None without a device plane
(a rehearsal) and on a program with no such kernel (every configuration
whose attention has no window, the parent)."""
import re

WINDOWED = re.compile(r"flash_\S*_window\S* custom-call:tpu_custom_call$")


def read(run):
    from chipbench.program_spans import kernel_share_pct
    return kernel_share_pct(run, WINDOWED)
