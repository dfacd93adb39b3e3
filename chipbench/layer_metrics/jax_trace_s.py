"""Seconds, before the window, in which JAX traced Python to a jaxpr:
the union of the ``compile::jax_trace`` spans (the program emits one
for each ``/jax/core/compile/jaxpr_trace_duration`` event; inner jits
fire inside outer ones, hence the union). This is where the executor's
``trace_block`` runs every op's compute rule. None without a device
plane (a rehearsal) and on a program without these spans."""


def read(run):
    from chipbench.program_spans import setup_seconds
    return setup_seconds(run, ("compile::jax_trace",))
