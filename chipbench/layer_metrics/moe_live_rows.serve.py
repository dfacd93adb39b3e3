"""Rows an expert layer's grouped products multiply a decode step: what
the routing of the window's decode steps sent the experts, from the
layers' own counts (each decode program reports, with its tokens, the
rows every expert was sent summed over its layers; the engine's
``paddle_tpu_decode_expert_rows_total`` adds them up at its telemetry
step and the driver reads it as the window opens and as it closes), over
the window's steps and the expert layers. A live slot's token is one row
a layer and an empty slot's none, so it reads the mean number of
requests in flight — a little under the slots at 0.8 of the knee — and
``slots`` only if padding were routed. None on a run that is not a serve
run, for a program with no such counter (every other configuration, the
parent) and on a window without steps."""


def read(run):
    seen = run.get("experts_in_window")
    if run.get("kind") != "serve" or not seen or not seen["steps"] \
            or not run.get("expert_layers"):
        return None
    return seen["rows"] / (seen["steps"] * run["expert_layers"])
