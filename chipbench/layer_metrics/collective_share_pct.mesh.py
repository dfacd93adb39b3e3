"""Summed duration of the collective operations (all-reduce,
all-gather, reduce-scatter, all-to-all, collective-permute) on device 0
over the traced window's wall. Exposed or hidden is not told apart."""


def read(run):
    from chipbench.trace import COLLECTIVE
    red = run.get("reduced")
    if red is None or run.get("chips", 1) < 2 or not red.window_s:
        return None
    return red.seconds(COLLECTIVE, 0) / red.window_s * 100.0
