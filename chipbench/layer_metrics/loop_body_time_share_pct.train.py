"""Device time of a counted loop's body: the instructions whose program
op lies in a loop's sub-block (a block path longer than one: the L
decoder blocks and the final norm of a looped stack, forward, and since
the op table hands a loop's grad op on to its sub-block's ops, their
backward), plus what stays on the loop op and its grad op themselves
(``static_rnn``, ``__vjp__.static_rnn``: the carry's copies, the stacked
outputs' updates), over device busy time on device 0 in the traced
window; see ``chipbench/program_ops.py``. What is left is the
embedding, the heads, the loss, the exit gate and the optimizer. None
without a device plane (a rehearsal), on a run that is not a train run,
on a program that keeps no op table and on a program without a loop
(the other configurations, the parent)."""

LOOP_TYPES = ("static_rnn", "__vjp__.static_rnn")


def read(run):
    from chipbench.program_ops import seconds_by_op, share_pct

    def in_loop(ref):
        return len(ref.block_path) > 1 or ref.op_type in LOOP_TYPES

    by_op = seconds_by_op(run)
    if not by_op or not any(ref is not None and in_loop(ref)
                            for ref in by_op):
        return None
    return share_pct(run, in_loop)
