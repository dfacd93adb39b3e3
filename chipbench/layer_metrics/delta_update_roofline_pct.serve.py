"""The least time the chip could take for the delta-rule state update
calls of the traced window (chipbench/arith_olmo_hybrid.py
``delta_update_seconds``: a layer's matrix state of every slot read and
written once, float32, beside the rows and the keys and queries that
drive it; bytes bind at 0.9 FLOPs a byte) over the time of the kernels
the program names ``delta_state_update`` on device 0. The calls are
counted in the trace, and only the WHOLE calls of the window: one its
edge clips would count its bytes against a part of its time, so a
reading above 100 is a counting fault. None without a device plane, for
a configuration with no ``arith`` of this kind and on a program with no
such kernel (the parent)."""
import re

DELTA_UPDATE = re.compile(
    r"^delta_state_update\S* custom-call:tpu_custom_call$")


def read(run):
    import importlib
    red, peaks, cfg = run.get("reduced"), run.get("peaks"), run["config"]
    if red is None or not peaks or not run.get("slots") \
            or "arith" not in cfg:
        return None
    arith = importlib.import_module(cfg["arith"])
    if not hasattr(arith, "delta_update_seconds"):
        return None
    t0, t1 = red.t0, red.t1
    calls = [d for n, s, d in red.ops[0]
             if DELTA_UPDATE.search(n) and s > t0 and s + d < t1]
    if not calls:
        return None
    least = arith.delta_update_seconds(run["slots"], peaks, **cfg)
    return least["seconds"] * len(calls) / (sum(calls) * 1e-9) * 100.0
