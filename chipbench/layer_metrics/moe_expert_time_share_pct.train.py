"""Time of the expert layer's own device operations over device busy
time on device 0 in the traced window: the grouped products and the
sort of the dispatch. ``jax.lax.ragged_dot`` reaches a v5e as Mosaic
custom calls that XLA names ``ragged-dot-metadata`` (group sizes to
tile lists) and ``ragged-dot-none[.N]`` (the products, forward and both
gradients), and the two argsorts of ops/moe_ops.py as ``sort[.N]``
instructions; the program sorts nowhere else. The row gathers of the
dispatch and the combine are fused by XLA into ``fusion.N`` operations
that no name tells from others: they are NOT in this share (PERF.md
section 7). None without a device plane (a rehearsal) and on a program
with no such operation."""
import re

EXPERTS = re.compile(r"^(ragged-dot\S* custom-call:tpu_custom_call"
                     r"|sort\S* sort)$")


def read(run):
    from chipbench.program_spans import kernel_share_pct
    return kernel_share_pct(run, EXPERTS)
