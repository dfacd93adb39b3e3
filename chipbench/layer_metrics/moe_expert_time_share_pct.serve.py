"""Time of the expert layers' own device operations over device busy
time on device 0 in the traced window of a serve run: the grouped
products (``jax.lax.ragged_dot``: Mosaic custom calls XLA names
``ragged-dot-*``) and the sorts of the dispatch (``sort[.N]``; the
program sorts nowhere else), of decode steps and prefills alike. The
row gathers of dispatch and combine are fused into ``fusion.N``
operations no name tells from others: NOT in this share. None without a
device plane (a rehearsal), on a run that is not a serve run and on a
program with no such operation (every other configuration, the
parent)."""
import re

EXPERTS = re.compile(r"^(ragged-dot\S* custom-call:tpu_custom_call"
                     r"|sort\S* sort)$")


def read(run):
    from chipbench.program_spans import kernel_share_pct
    if run.get("kind") != "serve":
        return None
    return kernel_share_pct(run, EXPERTS)
