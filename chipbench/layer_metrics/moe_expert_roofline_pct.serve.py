"""The least time the chip could take for the grouped products of the
traced window's decode steps (the configuration's ``arith``
``experts_seconds``: every expert a step's picks reached read once —
three matrices — and each routed row in and out of each product; at 6
rows an expert bytes bind) over the time of the ``ragged-dot`` custom
calls that ran INSIDE the window's ``generation::decode_step`` spans on
device 0 (a prefill's grouped products have the same name and are left
out with their time). Experts read and rows are the engine's counters,
read as the window opens and closes on the host's clock, the calls are
the trace's: the two windows differ by at most a step at each edge, 1
in ~100. Above 100 would be a counting fault. None without a device
plane, for a configuration with no ``arith`` of this kind and on a
program with no such counter or call (the parent)."""
import re

GROUPED = re.compile(r"^ragged-dot\S* custom-call:tpu_custom_call$")


def read(run):
    import importlib
    from chipbench.decode_steps import inside, intervals_by_bucket
    red, peaks, cfg = run.get("reduced"), run.get("peaks"), run["config"]
    seen = run.get("experts_in_window")
    if red is None or not peaks or not seen or not seen["steps"] \
            or "arith" not in cfg:
        return None
    arith = importlib.import_module(cfg["arith"])
    if not hasattr(arith, "experts_seconds"):
        return None
    steps = sorted(i for spans in intervals_by_bucket(run).values()
                   for i in spans)
    calls = inside(red.ops[0], steps, GROUPED)
    if not calls:
        return None
    least = arith.experts_seconds(seen["experts_read"], seen["rows"], peaks,
                                  **cfg)
    return least["seconds"] / (sum(d for _n, _s, d in calls) * 1e-9) * 100.0
