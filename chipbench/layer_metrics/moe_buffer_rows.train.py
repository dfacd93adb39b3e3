"""Rows an expert layer's dispatch, grouped products, activation and
combine ran over a step: the blocks of sorted rows that held the
step's live rows (``paddle_tpu/ops/moe_ops.py block_rows`` a block, as
many as the live rows call for), which the program tallies itself
beside them (the fourth entry of each ``layers.moe_experts`` layer's
persistable ``<layer>.live_rows``: the blocks' rows summed over the
steps run; read here from the program's scope once the run is over).
The mean over every step the program ran, set-up's included, and over
the expert layers, as ``moe_live_rows.train`` is: the two read side by
side say how much of the row work moved a token. None for a program
with no such layer, for one whose tally has no such entry (the parent
of PR 42: three entries, every pass over the worst case), on a run
without steps and without a device plane (a rehearsal: at toy sizes a
block is the whole worst case, and the number would say nothing of the
chip's)."""


def read(run):
    if (run.get("kind") != "train" or not run.get("steps")
            or run.get("reduced") is None):
        return None
    import numpy as np
    import paddle_tpu as pt
    scope = pt.global_scope()
    tallies = [np.asarray(scope.get(n)) for n in scope.local_names()
               if n.endswith(".live_rows")]
    means = [float(t[3] / t[1]) for t in tallies
             if t.shape[0] > 3 and t[1] > 0]
    return sum(means) / len(means) if means else None
