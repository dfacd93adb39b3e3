"""Which op of the Program an instruction of its compiled step belongs to.

`core/executor.py:_trace_ops` runs every op's rule under two nested
``jax.named_scope``s, the op's type and then the op itself as
``analysis/cost_model.OpCost`` names it, so every HLO instruction the
rule emits carries ``op_name="jit(step_fn)/matmul/b0.412/dot_general"``
in its metadata, through XLA's passes and into the compiled module. A
device trace names an instruction and nothing else ("XLA Ops" events
carry the HLO line with EMPTY metadata); `parse` reads a compiled
module's text into the table that joins the two, and
`profiler.device_op_times` reduces a trace through it.

What a row can and cannot say:

* A fusion is charged to the fusion instruction's own ``op_name``, which
  is the one XLA kept of its body's (the root's, as a rule). `mixed`
  lists the fusions whose body holds instructions of more than one
  program op, so a reader can say how far a row may be off.
* A grad op's instructions go to the grad op (``__vjp__.<forward
  type>``) whether it replays its forward op or applies the pullback
  that op left (``.../__vjp__.relu/b0.12/transpose(relu)/b0.2/...``: the
  FIRST scope decides). A forward op with a sub-block (``while``,
  ``conditional_block``, ``static_rnn``) hands its instructions on to
  the op of the sub-block that emitted them (the LAST scope), and so
  does its GRAD op, whose pullback is the loop's transpose
  (``.../__vjp__.static_rnn/b0.98/transpose(jvp())/while/body/
  closed_call/mul/b0.1.7/dot_general``): where the last scope lies in
  a deeper block than the grad op's own, the instruction goes to that
  sub-block op as ``__vjp__.<its type>``, role ``backward``, at the
  sub-block's path — a looped stack's backward keeps its rows by type
  (``__vjp__.mul``, the backward flash kernel under
  ``__vjp__.scaled_dot_product_attention``). So does what the
  transpose computes AGAIN of the body, which runs under
  ``jax.checkpoint`` (``.../__vjp__.static_rnn/b0.98/transpose(jvp())/
  while/body/closed_call/checkpoint/rematted_computation/rms_norm/
  b0.1.3/mul``: a recomputed norm is a row of ``__vjp__.rms_norm``).
  What the transpose emits outside any body op (the carry's slices and
  updates) stays on the grad op.
* An inner ``jax.jit`` that several sites share is lowered once, under
  its first site's scope: rows by TYPE are exact, rows by op index put
  every site's time on the first.
* A module that came out of the persistent compile cache carries the
  metadata of the tree that compiled it (`parse`): rows by type always,
  rows by op only from a tree that has the op scope.
* An instruction whose metadata names no program op (XLA's own copies,
  ``copy-start``/``copy-done``, parameters) is in `unmapped`, never
  guessed at.
"""
from __future__ import annotations

import re
from typing import Dict, FrozenSet, Mapping, NamedTuple, Optional, Tuple

class OpRef(NamedTuple):
    """A program op as `cost_model.OpCost` identifies it, and its role."""
    op_type: str
    role: str
    block_path: Tuple[int, ...]
    op_index: int


class OpTable(NamedTuple):
    module: str                    # "jit_step_fn"
    ops: Dict[str, OpRef]          # instruction name -> its program op
    unmapped: Dict[str, str]       # instruction name -> opcode
    mixed: FrozenSet[str]          # fusions over more than one program op


def scope(block_path: Tuple[int, ...], op_index: int) -> str:
    """The second scope component: ``b0.412`` for op 412 of block 0,
    ``b0.2.7`` for op 7 of block 2 under block 0. Content of the
    program alone (no uid, no counter): it reaches the HLO and so the
    persistent compile cache's key."""
    return "b" + ".".join(map(str, block_path)) + f".{op_index}"


_WHERE = re.compile(r"^b((?:\d+\.)+)(\d+)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([^\s(]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([^\s=]+) = (.*)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,)}]+)")


def optimizer_types() -> FrozenSet[str]:
    from ..analysis import cost_model
    return frozenset(cost_model._OPTIMIZER_FLOPS) \
        | frozenset(cost_model._SPARSE_OPTIMIZER_FLOPS)


def scope_type(op) -> str:
    """The first scope component of an op: its type, and for a grad op
    ``__vjp__.<forward type>``."""
    if op.type == "__vjp__" and op.attrs.get("fwd_op"):
        return "__vjp__." + op.attrs["fwd_op"]["type"]
    return op.type


def _role(op_type: str, after_grad: bool,
          optimizers: FrozenSet[str]) -> str:
    if op_type.startswith("__vjp__"):
        return "backward"
    if op_type in optimizers:
        return "optimizer"
    return "other" if after_grad else "forward"


def program_ops(block) -> Dict[Tuple[int, int], Tuple[str, str]]:
    """{(block idx, op index): (role, scope type)} over the program of
    `block`. What follows a block's first grad op and is neither a grad
    op nor an optimizer op (gradient sums, clipping, casts of master
    weights, the learning-rate schedule) has the role ``other``."""
    optimizers = optimizer_types()
    out = {}
    for blk in block.program.blocks:
        after_grad = False
        for i, op in enumerate(blk.ops):
            kind = scope_type(op)
            out[(blk.idx, i)] = (_role(kind, after_grad, optimizers), kind)
            after_grad = after_grad or op.type == "__vjp__"
    return out


def _op_ref(op_name: str, known: Mapping, types: FrozenSet[str],
            optimizers: FrozenSet[str]) -> Optional[OpRef]:
    """The program op an ``op_name`` names, or None. XLA joins the names
    of instructions it merges with ``;``: the first that names an op.
    A name with the type scope and no op scope (see `parse`) gives the
    type alone: block path ``()``, index -1."""
    for one in op_name.split(";"):
        parts = one.split("/")
        found = [(parts[i - 1], m) for i, m in
                 ((i, _WHERE.match(p)) for i, p in enumerate(parts))
                 if m and i > 0]
        if not found:
            continue
        op_type, m = found[0]
        is_grad = op_type.startswith("__vjp__")
        # a grad op whose last scope lies in a deeper block: a loop's
        handed_on = is_grad and \
            found[-1][1].group(1).count(".") > m.group(1).count(".")
        if handed_on or not is_grad:
            op_type, m = found[-1]
        if handed_on and not op_type.startswith("__vjp__"):
            op_type = "__vjp__." + op_type
        path = tuple(int(x) for x in m.group(1).split(".") if x)
        index = int(m.group(2))
        role = "backward" if handed_on else (
            known.get((path[-1], index), (None,))[0]
            or _role(op_type, False, optimizers))
        return OpRef(op_type, role, path, index)
    for one in op_name.split(";"):
        inner = [p for p in one.split("/") if not p.startswith("jit(")]
        if inner and inner[0] in types:
            return OpRef(inner[0], _role(inner[0], False, optimizers),
                         (), -1)
    return None


def parse(hlo_text: str, known: Optional[Mapping] = None) -> OpTable:
    """The table of one compiled module's text (``compiled.as_text()``),
    over every computation of it: the entry's instructions, those of
    ``while`` bodies and branches (a trace shows them by name too) and
    those inside fused computations (which decide `mixed`). `known` is
    the module's `program_ops`; without it no op reads ``other``.

    JAX's persistent compile cache leaves metadata out of its key
    (``jax_compilation_cache_include_metadata_in_key`` is off: a scope
    string can never cause a miss), so a hit hands back the executable
    with the metadata of the tree that WROTE the entry. One written
    before the op scope existed names the type and no op
    (``jit(step_fn)/mul/dot_general``): where the first scope is a type
    of `known`, the instruction maps to the type alone. Rows by type,
    and so the roles backward and optimizer, read the same from either;
    rows by op need a compile of this tree's own."""
    known = known or {}
    types = frozenset(kind for _role_, kind in known.values())
    optimizers = optimizer_types()
    head = re.match(r"HloModule ([^\s,]+)", hlo_text)
    ops: Dict[str, OpRef] = {}
    unmapped: Dict[str, str] = {}
    inside: Dict[str, set] = {}      # computation -> program ops in it
    fusions = []                     # (instruction, computation called)
    current = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                current = inside.setdefault(c.group(1), set())
            continue
        name, rest = m.groups()
        code = _OPCODE.search(rest)
        opcode = code.group(1) if code else "?"
        named = _OP_NAME.search(rest)
        ref = named and _op_ref(named.group(1), known, types, optimizers)
        if ref:
            ops[name] = ref
            if current is not None:
                current.add(ref[2:])
        else:
            unmapped[name] = opcode
        if opcode == "fusion":
            called = _CALLS.search(rest)
            if called:
                fusions.append((name, called.group(1)))
    mixed = frozenset(n for n, c in fusions if len(inside.get(c, ())) > 1)
    return OpTable(head.group(1) if head else "", ops, unmapped, mixed)
