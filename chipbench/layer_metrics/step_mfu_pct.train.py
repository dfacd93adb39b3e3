"""Model FLOPs utilisation of the whole train step: the
configuration's ``train_flops`` (forward + backward from shapes, its
``arith`` module; recomputation, norms, softmax and the optimizer are
not counted) times the steps that ended in the window, over the
window's wall time and the bf16 peak of the chips the cell runs on. It
is the share of the peak a step as a whole reaches, idle time and every
non-matmul operation included, so no gain on this cell can pass what it
leaves. None off the chip (a rehearsal has no table of peaks) and on a
run without steps."""


def read(run):
    peaks, steps = run.get("peaks"), run.get("steps")
    if not peaks or not steps or not run.get("step_flops") \
            or run.get("reduced") is None:
        return None
    t0, t1 = run["window"]
    peak = peaks["bf16_flops_per_s"] * run["chips"]
    return run["step_flops"] * len(steps) / (t1 - t0) / peak * 100.0
