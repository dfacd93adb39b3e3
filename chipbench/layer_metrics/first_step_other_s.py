"""Wall seconds of the first call of every program the cell warms (the
train cells' warm-up steps; the serve cell's first prefill and decode
per bucket) less the backend-compile seconds JAX reported inside those
calls. A RESIDUAL, not a span: rewrite passes, gates, the cost and
memory plans, JAX tracing and lowering, the persistent cache's look-up
and the first execution all sit in it (and, for train, the warm-up's
steady steps, well under a second)."""


def read(run):
    calls = run.get("first_calls")
    if not calls:
        return None
    wall = sum(e - b for b, e in calls)
    compiling = sum(run["spans"].compile_seconds(b, e) for b, e in calls)
    return max(0.0, wall - compiling)
