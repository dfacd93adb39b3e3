"""Mosaic custom-call time over device busy time on device 0 in the
traced window. The train step's custom calls are all flash attention
(PERF.md, PR 22: 72 per step)."""


def read(run):
    from chipbench.trace import CUSTOM_CALL
    red = run.get("reduced")
    if red is None:
        return None
    kernel, busy = red.seconds(CUSTOM_CALL, 0), red.busy_on(0)
    return kernel / busy * 100.0 if kernel and busy else None
