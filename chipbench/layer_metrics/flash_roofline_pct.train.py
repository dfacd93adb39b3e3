"""The least time the chip could take for the flash calls of the
traced steps (chipbench/arith.py: the larger of FLOPs over the bf16
peak and bytes over the HBM bandwidth; at sequence 2048 FLOPs bind)
over the custom-call time measured on device 0."""


def read(run):
    from chipbench.arith import roofline_seconds
    from chipbench.trace import CUSTOM_CALL
    red, cost = run.get("reduced"), run.get("flash_cost")
    if red is None or not cost or not run.get("peaks"):
        return None
    kernel = red.seconds(CUSTOM_CALL, 0)
    if not kernel:
        return None
    least = roofline_seconds(cost["flops"], cost["bytes"], run["peaks"])
    return least["seconds"] * len(run["steps"]) / kernel * 100.0
