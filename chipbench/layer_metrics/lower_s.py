"""Seconds, before the window, in which JAX lowered jaxprs to
StableHLO, the Mosaic kernels' lowering included: the union of the
``compile::lower`` spans (one for each
``/jax/core/compile/jaxpr_to_mlir_module_duration`` event). No cache
skips this: the persistent cache's key is computed from the lowered
module. None without a device plane (a rehearsal) and on a program
without these spans."""


def read(run):
    from chipbench.program_spans import setup_seconds
    return setup_seconds(run, ("compile::lower",))
