"""Sum of JAX's ``/jax/core/compile/backend_compile_duration`` events
from process start to the window: XLA compilation, or the persistent
cache's retrieval where it hits."""


def read(run):
    return run.get("compile_backend_s")
