"""Rows an expert layer's grouped products multiply a step: the
assignments the routing sent to the experts this chip holds, which the
program tallies itself (``layers.moe_experts`` keeps a persistable
``<layer>.live_rows`` = their sum over the steps run, the steps, the
last step's; read here from the program's scope once the run is over).
The mean over every step the program ran, set-up's included (the tally
starts with the program: 3 warm-up steps beside the window's), and
over the expert layers. Under a balanced routing it is tokens x experts
a token x held / total, and the configuration's ``train_flops`` books
exactly that; what is read above it is work the step does and
``step_mfu_pct.train`` does not count. None for a program with no
such layer (the other configurations, the parent) and on a run without
steps."""


def read(run):
    if run.get("kind") != "train" or not run.get("steps"):
        return None
    import numpy as np
    import paddle_tpu as pt
    scope = pt.global_scope()
    tallies = [np.asarray(scope.get(n)) for n in scope.local_names()
               if n.endswith(".live_rows")]
    means = [float(t[0] / t[1]) for t in tallies if t[1] > 0]
    return sum(means) / len(means) if means else None
