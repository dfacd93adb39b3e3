"""The least time the chip could take for the recurrent-state update
calls of the traced window (chipbench/arith_granite.py
``ssm_update_seconds``: a layer's state of every slot read and written
once, float32, beside the rows that drive it; bytes bind at 0.6 FLOPs a
byte) over the time of the kernels the program names
``ssm_state_update`` on device 0. The calls are counted in the trace, so
a call the window cuts counts whole against its clipped time: a reading
above 100 would be a counting fault. None without a device plane, for a
configuration with no ``arith`` of this kind and on a program with no
such kernel (the parent)."""
import re

SSM_UPDATE = re.compile(r"^ssm_state_update\S* custom-call:tpu_custom_call$")


def read(run):
    import importlib
    red, peaks, cfg = run.get("reduced"), run.get("peaks"), run["config"]
    if red is None or not peaks or not run.get("slots") \
            or "arith" not in cfg:
        return None
    arith = importlib.import_module(cfg["arith"])
    if not hasattr(arith, "ssm_update_seconds"):
        return None
    t0, t1 = red.t0, red.t1
    # whole calls only: one the window's edge clips would count its
    # bytes against a part of its time
    calls = [d for n, s, d in red.ops[0]
             if SSM_UPDATE.search(n) and s > t0 and s + d < t1]
    if not calls:
        return None
    least = arith.ssm_update_seconds(run["slots"], peaks, **cfg)
    return least["seconds"] * len(calls) / (sum(calls) * 1e-9) * 100.0
