"""The least time the chip could take for the flash-attention calls of
the traced steps (the configuration's ``flash_cost``: the larger of
FLOPs over the bf16 peak and bytes over the HBM bandwidth) over the
time of the kernels the program NAMES ``flash_fwd``, ``flash_bwd_dq``
and ``flash_bwd_dkv`` on device 0. ``flash_roofline_pct.train`` divides
by every Mosaic custom call's time, which is right only where flash
attention is the step's only kernel; a step whose grouped expert
products are Mosaic calls too (``ragged-dot-*``) needs the names. None
without a device plane and on a program whose kernels have no name."""
import re

FLASH = re.compile(r"flash_(fwd|bwd_dq|bwd_dkv)\S* "
                   r"custom-call:tpu_custom_call$")


def read(run):
    from chipbench.arith import roofline_seconds
    red, cost = run.get("reduced"), run.get("flash_cost")
    if red is None or not cost or not run.get("peaks") \
            or not run.get("steps"):
        return None
    kernel = red.seconds(FLASH, 0)
    if not kernel:
        return None
    least = roofline_seconds(cost["flops"], cost["bytes"], run["peaks"])
    return least["seconds"] * len(run["steps"]) / kernel * 100.0
