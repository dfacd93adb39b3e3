"""What the readers of device time by program op share. The program
keeps, for every step program it compiled, the table from the name of an
instruction of the compiled module to the op of the ``Program`` whose
rule emitted it (``paddle_tpu.core.executor.compiled_programs()``, each
entry's ``op_table()``: built on this first ask, after the run, from the
module lowered again): ``ops`` maps an instruction name to ``(op_type,
role, block_path, op_index)``, role one of forward / backward /
optimizer / other; what XLA gave no ``op_name`` (its own copies,
``copy-start`` / ``copy-done``) is not in it. The readers join that with
``run["reduced"].ops[0]``, the ``[<instruction> <opcode>, start, dur]``
of device 0 in the traced window, and divide by ``busy_on(0)``.

Which table: of the entries the process compiled, the one under which
most of the window's device time maps (asked costliest program first,
and no further once a table maps over half of it). The startup program
shares instruction names (``fusion.1``) with the step program but not
its time.

Every reader here returns None when ``run["reduced"]`` is None (no
device plane: a rehearsal off the chip), on a run that is not a train
run, and on a program that keeps no such table (the parent of the PR
that added it)."""
from __future__ import annotations


def tables():
    """The op tables of the step programs the process compiled, the
    costliest by the cost model's FLOPs first, each built as it is
    asked for (a lowering and a cache retrieval; the startup program's
    is seldom needed); nothing on a program without them."""
    try:
        from paddle_tpu.core.executor import compiled_programs
    except ImportError:
        return
    for entry in sorted(compiled_programs(), key=lambda e: -(
            getattr(e.cost, "flops", 0) or 0)):
        yield entry.op_table()


def seconds_by_op(run):
    """{(op_type, role, block_path, op_index) or None: seconds} over
    device 0's operations in the window, None for the instructions the
    table gives no program op; or None (see above). An instruction's
    seconds are its own: a ``while`` is an event around its body's."""
    if run.get("reduced") is None or run.get("kind") != "train":
        return None
    if "seconds_by_op" not in run:
        own, best, best_mapped = None, None, 0.0
        for table in tables():
            if own is None:
                from paddle_tpu.profiler import self_times
                own = self_times(run["reduced"].ops[0])
            by_op = {}
            for name, ns in own:
                ref = table.ops.get(name.split(" ", 1)[0])
                by_op[ref] = by_op.get(ref, 0.0) + ns * 1e-9
            mapped = sum(s for ref, s in by_op.items() if ref is not None)
            if mapped > best_mapped:
                best, best_mapped = by_op, mapped
            if mapped > 0.5 * sum(by_op.values()):
                break           # no other table can map more of it
        run["seconds_by_op"] = best
    return run["seconds_by_op"]


def share_pct(run, want):
    """Seconds of the instructions whose program op ``want(ref)``
    accepts, over device 0's busy time, in percent; or None."""
    by_op = seconds_by_op(run)
    busy = by_op and run["reduced"].busy_on(0)
    if not busy:
        return None
    return sum(s for ref, s in by_op.items()
               if ref is not None and want(ref)) / busy * 100.0


def role_share_pct(run, role):
    return share_pct(run, lambda ref: ref.role == role)


def type_share_pct(run, op_types):
    return share_pct(run, lambda ref: ref.op_type in op_types)
