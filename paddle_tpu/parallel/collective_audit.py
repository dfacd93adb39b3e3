"""Compiled-HLO collective inventory for SPMD layouts.

The reference makes its collectives explicit, auditable graph nodes
(reference: paddle/fluid/framework/details/nccl_all_reduce_op_handle.cc:30
— you can SEE the all-reduce in the SSA graph). Under GSPMD the
collectives are implicit — XLA inserts them from shardings — so this
module recovers them from the compiled HLO: which collective kinds run,
over which MESH AXES (classified from replica groups / permute pairs),
moving how many bytes. The multi-chip dry run prints this inventory and
asserts the expected collectives per axis, which is the scaling
evidence a single-chip environment permits: a layout that silently
loses its gradient all-reduce or its ring permute fails loudly.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import numpy as np

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
                "s32": 4, "u64": 8, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
                "f8e4m3fn": 1, "f8e5m2": 1}

_KINDS = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
          "collective-permute")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", shape_str):
        dt, dims = m.groups()
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


class Collective:
    __slots__ = ("kind", "bytes", "groups", "pairs", "axes", "tensors",
                 "op_name")

    def __init__(self, kind, nbytes, groups=None, pairs=None, tensors=(),
                 op_name=""):
        self.kind = kind
        self.bytes = nbytes
        # the arrays it carries, "bf16[16384,512]" each: XLA's combiner
        # packs several into one instruction, differently by backend,
        # so a layout's cost is counted in tensors, not instructions
        self.tensors = tuple(tensors)
        # the metadata's op_name (of ONE of a combined instruction's)
        self.op_name = op_name
        self.groups = groups    # list[list[int]] or None
        self.pairs = pairs      # list[(src, dst)] or None
        self.axes: Optional[Tuple[str, ...]] = None

    def __repr__(self):
        ax = "+".join(self.axes) if self.axes else "?"
        return f"<{self.kind} over {ax}: {self.bytes / 1e6:.2f}MB>"


def _decode_iota_groups(g, s, dims, perm) -> List[List[int]]:
    """XLA's iota replica-group v2 form `[G,S]<=[dims]T(perm)`: device
    ids 0..prod(dims)-1 reshaped to `dims`, transposed by `perm`, then
    reshaped to G groups of S."""
    ids = np.arange(int(np.prod(dims))).reshape(dims)
    if perm is not None:
        ids = ids.transpose(perm)
    return [[int(v) for v in row] for row in ids.reshape(g, s)]


def parse_collectives(hlo_text: str) -> List[Collective]:
    """Collective instructions (incl. -start forms) from HLO text.
    Handles both literal replica_groups={{0,1},{2,3}} and the iota
    form replica_groups=[G,S]<=[dims]T(perm)."""
    out = []
    for ln in hlo_text.splitlines():
        # the shape is everything between "= " and the opcode: a TPU
        # tuple shape nests parentheses in its tiled layouts
        # ((bf16[8192,512]{1,0:T(8,128)(2,1)}, ...)), so it cannot be
        # matched as one balanced group
        m = re.search(
            r"= (.+?) (all-reduce|reduce-scatter|all-gather"
            r"|all-to-all|collective-permute)(?:-start)?\(", ln)
        if not m:
            continue
        shape, kind = m.groups()
        groups = pairs = None
        if kind == "collective-permute":
            pm = re.search(
                r"source_target_pairs=\{((?:\{\d+,\s*\d+\},?)+)\}", ln)
            if pm:
                pairs = [tuple(int(x) for x in p.split(","))
                         for p in re.findall(r"\{(\d+,\s*\d+)\}",
                                             pm.group(1))]
        else:
            gm = re.search(
                r"replica_groups=\{((?:\{[\d,\s]*\},?)+)\}", ln)
            im = re.search(
                r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\]"
                r"(?:T\(([\d,]+)\))?", ln)
            if gm:
                groups = [[int(x) for x in g.split(",") if x.strip()]
                          for g in re.findall(r"\{([\d,\s]*)\}",
                                              gm.group(1))]
                groups = [g for g in groups if g]
            elif im:
                g, s, dims, perm = im.groups()
                groups = _decode_iota_groups(
                    int(g), int(s),
                    [int(d) for d in dims.split(",")],
                    [int(p) for p in perm.split(",")] if perm else None)
        name = re.search(r'op_name="([^"]*)"', ln)
        out.append(Collective(
            kind, _shape_bytes(shape), groups, pairs,
            tensors=[f"{dt}[{dims}]" for dt, dims in
                     re.findall(r"(\w+)\[([\d,]*)\]", shape)],
            op_name=name.group(1) if name else ""))
    return out


def classify(collectives: List[Collective], mesh) -> List[Collective]:
    """Tag each collective with the mesh-axis subset it communicates
    over: the set of axes whose device coordinate VARIES within a
    replica group (for grouped collectives) or DIFFERS between source
    and target of a non-self pair (for permutes). This attributes
    every well-formed collective — including composite-axis permutes
    such as GSPMD resharding swaps between two axes (pairs differing
    in both coordinates) and halo exchanges with identity self-pairs.
    Collectives that move nothing across chips (all self-pairs /
    singleton groups) are tagged ("local",)."""
    names = list(mesh.axis_names)
    shape = [mesh.shape[n] for n in names]
    n_dev = int(np.prod(shape))
    coords = {i: np.unravel_index(i, shape) for i in range(n_dev)}

    def _order(axset) -> Tuple[str, ...]:
        return tuple(n for n in names if n in axset)

    for c in collectives:
        varying = set()
        if c.groups:
            for g in c.groups:
                if len(g) < 2:
                    continue
                base = coords[g[0]]
                for dev in g[1:]:
                    for ai, name in enumerate(names):
                        if coords[dev][ai] != base[ai]:
                            varying.add(name)
            c.axes = _order(varying) if varying else ("local",)
        elif c.pairs:
            for s, d in c.pairs:
                if s == d:
                    continue
                for ai, name in enumerate(names):
                    if coords[s][ai] != coords[d][ai]:
                        varying.add(name)
            c.axes = _order(varying) if varying else ("local",)
        elif c.kind != "collective-permute":
            # replica_groups={} (or absent): one group of ALL devices
            c.axes = tuple(names)
    return collectives


def inventory(hlo_text: str, mesh) -> Dict[Tuple[str, Tuple[str, ...]],
                                           Tuple[int, int]]:
    """{(kind, axes): (count, total_bytes)} for one compiled program."""
    inv: Dict = {}
    for c in classify(parse_collectives(hlo_text), mesh):
        key = (c.kind, c.axes or ("?",))
        cnt, b = inv.get(key, (0, 0))
        inv[key] = (cnt + 1, b + c.bytes)
    return inv


def tensor_elements(tensor: str) -> int:
    """Elements of one of `Collective.tensors` ("bf16[16384,512]")."""
    dims = tensor[tensor.index("[") + 1:-1]
    return int(np.prod([int(d) for d in dims.split(",") if d]))


def tensors_over(hlo_text: str, mesh, axis: str,
                 min_elements: int = 0) -> Dict[Tuple[str, str], int]:
    """{(kind, "dtype[dims]"): tensors} of the collectives whose axis
    set contains `axis`, a tuple's elements counted one by one, arrays
    under `min_elements` left out (activation-sized ones: pass the
    elements of one device's activation)."""
    out: Dict[Tuple[str, str], int] = {}
    for c in classify(parse_collectives(hlo_text), mesh):
        if axis not in (c.axes or ()):
            continue
        for t in c.tensors:
            if tensor_elements(t) >= min_elements:
                out[(c.kind, t)] = out.get((c.kind, t), 0) + 1
    return out


def format_inventory(inv) -> str:
    lines = []
    for (kind, axes), (cnt, b) in sorted(inv.items(),
                                         key=lambda kv: -kv[1][1]):
        lines.append(f"  {kind:20s} over {'+'.join(axes):18s} "
                     f"x{cnt:3d}  {b / 1e6:10.2f} MB")
    return "\n".join(lines) if lines else "  (no collectives)"


def axis_bytes(inv, kinds=None) -> Dict[str, int]:
    """Total estimated bytes per mesh axis (a collective over a
    composite axis set contributes its bytes to each member axis),
    optionally restricted to a set of collective kinds."""
    out: Dict[str, int] = {}
    for (kind, axes), (_cnt, b) in inv.items():
        if kinds is not None and kind not in kinds:
            continue
        for ax in axes:
            if ax not in ("?", "local"):
                out[ax] = out.get(ax, 0) + b
    return out


def assert_collectives(inv, expectations, forbid=()) -> None:
    """expectations: list of (kinds, axis) or (kinds, axis, min_bytes)
    — at least one collective whose kind is in `kinds` and whose axis
    set CONTAINS `axis` must exist (GSPMD may legally merge axes, e.g.
    one all-reduce over data+seq for gradients replicated across
    both); with min_bytes, the summed bytes of the matching rows must
    reach it (per-axis byte accounting, not just presence).

    `forbid`: list of (kinds, axis) that must NOT appear — rejects a
    misrouted layout (e.g. a ring permute landing on the wrong axis).

    Any row the classifier could not attribute (axes == ("?",)) fails
    the audit unconditionally: an unattributed collective is exactly
    the kind of silent misrouting this audit exists to catch."""
    unattributed = [(k, cnt, b) for (k, axes), (cnt, b) in inv.items()
                    if "?" in axes]
    if unattributed:
        raise AssertionError(
            "unattributed collectives in inventory (classifier could "
            f"not assign mesh axes): {unattributed}\n"
            + format_inventory(inv))
    for exp in expectations:
        kinds, axis = exp[0], exp[1]
        min_bytes = exp[2] if len(exp) > 2 else None
        rows = [(cnt, b) for (kind, axes), (cnt, b) in inv.items()
                if kind in kinds and axis in axes]
        if not rows:
            raise AssertionError(
                f"expected a {'/'.join(kinds)} collective over axis "
                f"{axis!r}; inventory:\n" + format_inventory(inv))
        if min_bytes is not None:
            got = sum(b for _c, b in rows)
            if got < min_bytes:
                raise AssertionError(
                    f"{'/'.join(kinds)} over {axis!r}: {got} bytes < "
                    f"expected minimum {min_bytes}; inventory:\n"
                    + format_inventory(inv))
    for kinds, axis in forbid:
        rows = [(kind, axes) for (kind, axes), _ in inv.items()
                if kind in kinds and axis in axes]
        if rows:
            raise AssertionError(
                f"forbidden collective present: {rows} over {axis!r}; "
                "inventory:\n" + format_inventory(inv))


def aot_compiled_for(exe, program):
    """AOT re-lower + compile the cached executable for `program` in
    executor `exe`, with the abstract arguments of the run that compiled
    it: the look-up by uid in front of `CompiledProgram.lower_again`,
    the one implementation of lowering a cache entry again (used by the
    collective audit, the benchmark's memory reading and the op
    table). The entry keeps its own abstract values: no scope, feed or
    open run is needed."""
    uid = program.desc.uid if hasattr(program, "desc") else program.uid
    entry = next(v for k, v in exe._cache.items() if k[0] == uid)
    return entry.lower_again()


def compiled_hlo_for(exe, program) -> str:
    """Compiled HLO text of the (single) cached executable for
    `program` in executor `exe`."""
    return aot_compiled_for(exe, program).as_text()
