"""Device time of the instructions that the loss ops emitted, forward
and backward (op types ``softmax_with_cross_entropy`` and
``__vjp__.softmax_with_cross_entropy``: the softmax over the
vocabulary and the cross entropy, not the head's matmul), over device
busy time on device 0 in the traced window; see
``chipbench/program_ops.py``. None without a device plane (a
rehearsal), on a run that is not a train run and on a program that
keeps no op table."""


def read(run):
    from chipbench.program_ops import type_share_pct
    return type_share_pct(run, ("softmax_with_cross_entropy",
                                "__vjp__.softmax_with_cross_entropy"))
