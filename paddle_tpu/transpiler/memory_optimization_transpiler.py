"""Memory-optimization transpiler: liveness analysis -> early release.

Reference behavior (memory_optimization_transpiler.py:40-343):
ControlFlowGraph dataflow analysis over the ProgramDesc, then in-place
var reuse so a long unrolled RNN fits memory. TPU-native delta, stated
plainly: XLA's buffer assignment already performs in-place reuse and
liveness-driven allocation *within* the compiled executable, so the
reference's main trick is free. What is NOT free is trace-time
liveness: every intermediate jax tracer the lowering keeps alive becomes
a live value XLA must treat as requested, and donation hints. This pass
therefore:

  1. builds the same ControlFlowGraph liveness the reference builds;
  2. annotates each op with `__dead_vars__` — non-persistable vars whose
     last use it is; the executor's trace loop drops them from the
     tracing env (executor honors the annotation, core/executor.py),
     shortening tracer lifetimes;
  3. exposes per-var lifetime stats so tests/tools can assert reuse.

release_memory() is the reference's lighter sibling: annotation only, no
reordering (here they share the implementation).

Successor note: the planner half of this lives in `analysis/memory.py`
(per-var live intervals, arena + free-at-last-use peak-HBM estimates,
the executor's pre-compile `hbm-oom` gate); new code should call
`analysis.memory.program_memory`. The in-place renaming half had a
successor too (PRs 8 and 20, a rewrite pipeline on every compile-cache
miss); PR 31 deleted it, because XLA's buffer assignment repeats every
renaming (PERF.md section 6). This module stays for the `__dead_vars__`
trace-time annotation and for reference API parity.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Set

from ..core.ir import SUB_BLOCK_ATTRS
from ..framework import Program

DEAD_VARS_ATTR = "__dead_vars__"


class ControlFlowGraph:
    """Forward-ordered single-block liveness (reference:
    ControlFlowGraph:40, _dataflow_analyze:97)."""

    def __init__(self, block):
        self.block = block
        self.uses: List[Set[str]] = []
        self.defs: List[Set[str]] = []
        for op in block.ops:
            self.uses.append(set(op.input_names()))
            self.defs.append(set(op.output_names()))

    def last_use_index(self) -> Dict[str, int]:
        """var -> index of the last op that reads or writes it."""
        last: Dict[str, int] = {}
        for i, (u, d) in enumerate(zip(self.uses, self.defs)):
            for n in u | d:
                last[n] = i
        return last

    def dead_after(self) -> List[Set[str]]:
        """For each op index, vars whose lifetime ends there."""
        last = self.last_use_index()
        out: List[Set[str]] = [set() for _ in self.block.ops]
        for name, idx in last.items():
            out[idx].add(name)
        return out


def _sub_block_refs(program: Program) -> Set[str]:
    """Every name a control-flow sub-block could read from the outer
    scope: all input/output names of every non-global block's ops, plus
    every string / list-of-string attr of ops that carry a sub-block
    (StaticRNN/While/cond reference outer vars via attrs like
    mem_new_names/cond_name, not via input slots). Conservative on
    purpose — liveness must never free what a nested block still needs."""
    refs: Set[str] = set()
    for block in program.desc.blocks[1:]:
        for op in block.ops:
            refs.update(op.input_names())
            refs.update(op.output_names())
    sub_attrs = SUB_BLOCK_ATTRS
    for block in program.desc.blocks:
        for op in block.ops:
            if not any(a in op.attrs for a in sub_attrs):
                continue
            for v in op.attrs.values():
                if isinstance(v, str):
                    refs.add(v)
                elif isinstance(v, (list, tuple)):
                    refs.update(x for x in v if isinstance(x, str))
    return refs


def _dead_after_lists(input_program: Program, skip: Set[str]):
    """Per-op releasable-var lists for the global block. The analysis runs
    in the native IR library (native/ir.cc liveness_program — including the
    conservative sub-block protection); the Python ControlFlowGraph below
    is the documented fallback if the native build is unavailable."""
    try:
        from ..native import ProgramIR
        handle = ProgramIR.from_json(input_program.desc.to_json())
        return [set(names) for names in handle.liveness(sorted(skip))]
    except Exception:
        block = input_program.desc.global_block
        dead = ControlFlowGraph(block).dead_after()
        out = []
        for dead_set in dead:
            releasable = set()
            for name in dead_set:
                v = block.find_var_recursive(name)
                if v is None or v.persistable or name in skip:
                    continue
                releasable.add(name)
            out.append(releasable)
        return out


def memory_optimize(input_program: Program, skip_opt_set: Optional[Set]
                    = None, print_log: bool = False, level: int = 0):
    """Annotate global-block ops with their dead-after var sets (in
    place). Sub-blocks are not annotated, and any var a sub-block might
    reference stays live (native liveness_program / _sub_block_refs)."""
    skip = set(skip_opt_set or ()) | _sub_block_refs(input_program)
    stats = {"annotated_ops": 0, "released_vars": 0}
    block = input_program.desc.global_block
    for op, releasable in zip(block.ops, _dead_after_lists(input_program,
                                                           skip)):
        if releasable:
            op.attrs[DEAD_VARS_ATTR] = sorted(releasable)
            stats["annotated_ops"] += 1
            stats["released_vars"] += len(releasable)
    input_program.desc._bump_version()
    if print_log:
        print(f"memory_optimize: {stats}")
    return stats


def release_memory(input_program: Program, skip_opt_set: Optional[Set]
                   = None):
    """Reference-compat alias (release_memory:340)."""
    return memory_optimize(input_program, skip_opt_set=skip_opt_set)
