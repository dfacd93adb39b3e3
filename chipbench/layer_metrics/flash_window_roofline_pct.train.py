"""The least time the chip could take for the WINDOWED flash-attention
calls of the traced steps (the configuration's ``flash_cost``:
``window_flops`` over the bf16 peak or ``window_bytes`` over the HBM
bandwidth, whichever is larger) over the time of the kernels the
program names ``flash_*_window`` on device 0. The work is the band's
own (query, key) pairs, W (W + 1) / 2 + (S - W) W a head, so a kernel
that computes tiles the window masks reads low, and a reading above 100
would be a counting fault. None without a device plane, for a
configuration whose ``flash_cost`` books no windowed site and on a
program with no such kernel (the parent)."""
import re

WINDOWED = re.compile(r"flash_\S*_window\S* custom-call:tpu_custom_call$")


def read(run):
    from chipbench.arith import roofline_seconds
    red, cost = run.get("reduced"), run.get("flash_cost") or {}
    if red is None or not cost.get("window_flops") \
            or not run.get("peaks") or not run.get("steps"):
        return None
    kernel = red.seconds(WINDOWED, 0)
    if not kernel:
        return None
    least = roofline_seconds(cost["window_flops"], cost["window_bytes"],
                             run["peaks"])
    return least["seconds"] * len(run["steps"]) / kernel * 100.0
