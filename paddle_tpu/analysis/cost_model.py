"""Static per-op cost model over ProgramDesc IR: FLOPs, bytes accessed,
parameter bytes.

The TensorFlow paper (PAPERS.md) treats per-op cost attribution as core
runtime infrastructure, and the XLA-fusion paper shows that FLOPs/bytes
per op is what locates fusion headroom. This module makes that
attribution a static property of every program: walk the reachable ops
(same traversal as the verifier's ``iter_ops``), resolve each operand's
shape from the declared + build-time-inferred VarDescs (dynamic ``-1``
dims bound from the feed shapes), and apply a per-op-type FLOP rule.

Accuracy contract (see KNOWN_GAPS "Performance attribution
boundaries"): matmul/conv-family ops are counted exactly (2 x MACs,
the same convention XLA's ``cost_analysis()`` uses for the dominant
terms); ``__vjp__`` grad ops are costed at 2x their embedded forward op
(the standard backward approximation — a train step totals ~3x the
forward); the ops of a ``static_rnn``'s sub-block are counted once a
trip (attr ``steps``, or the step input's length) and the loop's grad
op at twice that; everything else is approximated at one FLOP per output
element. ``bytes_accessed`` is the PRE-fusion operand traffic (every
op reads its inputs and writes its outputs) — an upper bound that XLA's
fusion then reduces, so arithmetic intensity from this model is a lower
bound on the compiled executable's.

The model is pure and cheap (one O(ops) walk, no trace, no device):
the executor attaches it to every compile-cache miss, and
``tools/lint_ir.py --cost`` prints it offline.
"""
from __future__ import annotations

import json
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..core import ir
from .passes import AnalysisPass, PassContext, iter_ops, register_pass

__all__ = ["OpCost", "ProgramCost", "program_cost", "CostModelPass",
           "ZERO_FLOP_OPS", "PRODUCT_OPS", "ITEMSIZE"]

_ITEMSIZE = {"float64": 8, "int64": 8, "float32": 4, "int32": 4,
             "float16": 2, "bfloat16": 2, "int16": 2, "int8": 1,
             "uint8": 1, "bool": 1}

#: public alias — the memory planner (analysis/memory.py) binds shapes
#: to bytes with the same table so the two analyses can never disagree
ITEMSIZE = _ITEMSIZE

#: ops that move/alias/select data without arithmetic — zero FLOPs by
#: contract (their bytes still count: a transpose is pure HBM traffic)
ZERO_FLOP_OPS = frozenset({
    "feed", "fetch", "assign", "share_data", "print", "shape",
    "fill_constant", "fill_constant_like",
    "fill_constant_batch_size_like", "fill_zeros_like", "fill",
    "assign_value", "reshape", "reshape2", "squeeze", "unsqueeze",
    "flatten", "transpose", "transpose2", "concat", "split", "slice",
    "strided_slice", "cast", "one_hot", "stack", "unstack", "expand",
    "expand_as", "tile", "reverse", "pad", "pad2d", "gather",
    "gather_nd", "lookup_table", "embedding_bag", "kv_cache_write",
    "kv_cache_append",
})

#: ops that ARE one contraction (`_flops_for` books 2 x MACs, the
#: output is the product): the outputs a static_rnn keeps of a pass,
#: with the norms and rotary embeddings they read, where it recomputes
#: every other op's (ops/control_flow_ops.py _name_kept). An attention
#: site is not among them: what a kernel computed of it is kept as the
#: kernel's output, by the primitive
PRODUCT_OPS = frozenset({
    "mul", "fanout_mul", "matmul", "conv2d", "depthwise_conv2d", "conv3d",
    "conv2d_transpose", "conv3d_transpose",
})

#: FLOPs per parameter element for each optimizer update rule (read +
#: decay + moment updates + write, counted from the compute rules)
_OPTIMIZER_FLOPS = {
    "sgd": 2, "momentum": 5, "adam": 12, "adagrad": 6, "adamax": 9,
    "adadelta": 9, "rmsprop": 9, "decayed_adagrad": 7, "ftrl": 12,
    "lars_momentum": 9, "proximal_gd": 6, "proximal_adagrad": 9,
}

#: same per-element rules for the sparse (touched-rows-only) variants
#: (ops/optimizer_ops.py sparse_sgd/sparse_adagrad/sparse_adam) — but
#: keyed on the DEDUPED row-grad numel, not Param numel: charging the
#: dense rule's Param numel would overcount by vocab/touched, which at
#: embedding scale is ~1e5x (PAPER sparse update path)
_SPARSE_OPTIMIZER_FLOPS = {
    "sparse_sgd": 2, "sparse_adagrad": 6, "sparse_adam": 12,
}


def _prod(dims: Sequence[int]) -> int:
    p = 1
    for d in dims:
        p *= int(d)
    return p


class _VarInfo:
    """Resolved operand: concrete shape (``-1`` bound), element count,
    bytes, and persistability."""

    __slots__ = ("name", "shape", "numel", "bytes", "persistable")

    def __init__(self, name: str, shape: List[int], itemsize: int,
                 persistable: bool):
        self.name = name
        self.shape = shape
        self.numel = _prod(shape)
        self.bytes = self.numel * itemsize
        self.persistable = persistable


class OpCost:
    """Cost of one op: FLOPs, operand bytes, parameter bytes read."""

    __slots__ = ("op_type", "block_path", "op_index", "flops",
                 "bytes_accessed", "param_bytes", "exact", "note")

    def __init__(self, op_type: str, block_path: Tuple[int, ...],
                 op_index: int, flops: int, bytes_accessed: int,
                 param_bytes: int, exact: bool,
                 note: Optional[str] = None):
        self.op_type = op_type
        self.block_path = tuple(block_path)
        self.op_index = op_index
        self.flops = int(flops)
        self.bytes_accessed = int(bytes_accessed)
        self.param_bytes = int(param_bytes)
        self.exact = bool(exact)
        self.note = note

    def to_dict(self) -> Dict:
        return {"op_type": self.op_type,
                "block_path": list(self.block_path),
                "op_index": self.op_index, "flops": self.flops,
                "bytes_accessed": self.bytes_accessed,
                "param_bytes": self.param_bytes, "exact": self.exact,
                "note": self.note}

    def __repr__(self):
        return (f"OpCost({self.op_type}, flops={self.flops}, "
                f"bytes={self.bytes_accessed})")


class ProgramCost:
    """Per-op costs plus program totals for one block tree.

    ``param_bytes`` deduplicates persistable vars program-wide (a param
    read by forward, backward, and its optimizer op counts once) —
    the resident-weights number; per-op ``param_bytes`` keeps every
    read for traffic accounting.
    """

    def __init__(self, ops: List[OpCost], param_bytes: int, batch: int,
                 block_idx: int, label: str = "program"):
        self.ops = ops
        self.param_bytes = int(param_bytes)
        self.batch = int(batch)
        self.block_idx = int(block_idx)
        self.label = label
        self.flops = sum(c.flops for c in ops)
        self.bytes_accessed = sum(c.bytes_accessed for c in ops)
        self.unresolved = sum(1 for c in ops
                              if c.note == "unresolved shapes")

    @property
    def arithmetic_intensity(self) -> float:
        """FLOPs per byte of pre-fusion operand traffic (a LOWER bound
        on the fused executable's intensity)."""
        return self.flops / self.bytes_accessed if self.bytes_accessed \
            else 0.0

    @property
    def exact_flops_fraction(self) -> float:
        """Fraction of total FLOPs carried by exactly-counted ops (the
        matmul/conv/optimizer family) — how much of the total is rule-
        derived rather than one-flop-per-element approximation."""
        if not self.flops:
            return 0.0
        return sum(c.flops for c in self.ops if c.exact) / self.flops

    def top_ops(self, limit: int = 20) -> List[OpCost]:
        return sorted(self.ops, key=lambda c: -c.flops)[:limit]

    def table(self, limit: int = 20) -> str:
        """Human-readable cost table, heaviest ops first."""
        lines = [
            f"cost {self.label} (block {self.block_idx}, "
            f"batch={self.batch}): {len(self.ops)} ops, "
            f"{self.flops / 1e9:.3f} GFLOP, "
            f"{self.bytes_accessed / 1e6:.2f} MB accessed, "
            f"{self.param_bytes / 1e6:.2f} MB params, "
            f"intensity {self.arithmetic_intensity:.1f} flop/B "
            f"({self.exact_flops_fraction * 100:.0f}% of flops exact, "
            f"{self.unresolved} op(s) unresolved)",
            f"{'flops':>14s} {'bytes':>12s} {'params':>10s}  op",
        ]
        for c in self.top_ops(limit):
            loc = "/".join(str(b) for b in c.block_path)
            note = f"  [{c.note}]" if c.note else ""
            lines.append(
                f"{c.flops:14d} {c.bytes_accessed:12d} "
                f"{c.param_bytes:10d}  b{loc}:op{c.op_index} "
                f"{c.op_type}{note}")
        if len(self.ops) > limit:
            lines.append(f"  ... {len(self.ops) - limit} more op(s)")
        return "\n".join(lines)

    def to_dict(self) -> Dict:
        return {
            "label": self.label, "block_idx": self.block_idx,
            "batch": self.batch, "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "param_bytes": self.param_bytes,
            "arithmetic_intensity": round(self.arithmetic_intensity, 4),
            "exact_flops_fraction":
                round(self.exact_flops_fraction, 4),
            "unresolved_ops": self.unresolved,
            "ops": [c.to_dict() for c in self.ops],
        }

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), **kw)

    def __repr__(self):
        return (f"ProgramCost({self.label}, flops={self.flops}, "
                f"bytes={self.bytes_accessed}, "
                f"params={self.param_bytes})")


# ---------------------------------------------------------------------------
# FLOP rules
# ---------------------------------------------------------------------------
def _attended_rows(op: ir.OpDesc, rows: int) -> int:
    """Key rows an attention site reads of the ``rows`` its K and V
    hold: all of them, or for a cached-decode site (KvLen), which is
    handed the whole cache, at most attr kv_bound — the static worst
    case, the live lengths being run-time data."""
    return min(rows, int(op.attrs.get("kv_bound", rows)))


def _flops_for(op: ir.OpDesc,
               lookup: Callable[[str], Optional[_VarInfo]]
               ) -> Tuple[Optional[int], bool, Optional[str]]:
    """(flops, exact, note) for one op; flops None = needed shapes are
    unresolvable (caller falls back to the generic estimate)."""

    def first(slot: str) -> Optional[_VarInfo]:
        names = op.input(slot)
        return lookup(names[0]) if names else None

    def out(slot: str) -> Optional[_VarInfo]:
        names = op.output(slot)
        return lookup(names[0]) if names else None

    t = op.type
    if t in ZERO_FLOP_OPS:
        return 0, True, None

    if t in ("mul", "fanout_mul"):      # fanout_mul: a product a Y
        x, ys = first("X"), [lookup(n) for n in op.input("Y")]
        if x is None or None in ys:
            return None, False, None
        xn = int(op.attrs.get("x_num_col_dims", 1))
        yn = int(op.attrs.get("y_num_col_dims", 1))
        m = _prod(x.shape[:xn])
        k = _prod(x.shape[xn:])
        return sum(2 * m * k * _prod(y.shape[yn:]) for y in ys), True, None

    if t == "matmul":
        x, o = first("X"), out("Out")
        if x is None or o is None or not x.shape:
            return None, False, None
        k = x.shape[-2] if op.attrs.get("transpose_X") and \
            len(x.shape) > 1 else x.shape[-1]
        return 2 * o.numel * int(k), True, None

    if t in ("conv2d", "depthwise_conv2d", "conv3d"):
        o, w = out("Output"), first("Filter")
        if o is None or w is None or len(w.shape) < 2:
            return None, False, None
        # filter [Cout, Cin/groups, *k]: MACs per output element
        return 2 * o.numel * _prod(w.shape[1:]), True, None

    if t in ("conv2d_transpose", "conv3d_transpose"):
        x, w = first("Input"), first("Filter")
        if x is None or w is None or len(w.shape) < 2:
            return None, False, None
        # filter [Cin, Cout, *k]: every input element hits Cout*k MACs
        return 2 * x.numel * _prod(w.shape[1:]), True, None

    if t in ("pool2d", "pool3d", "adaptive_pool2d"):
        o = out("Out")
        if o is None:
            return None, False, None
        k = op.attrs.get("ksize") or [1]
        return o.numel * _prod(k), False, None

    if t in ("softmax", "log_softmax"):
        x = first("X")
        return (None, False, None) if x is None else \
            (5 * x.numel, False, None)

    if t == "softmax_with_cross_entropy":
        x = first("Logits")
        return (None, False, None) if x is None else \
            (6 * x.numel, False, None)

    if t == "batch_norm":
        x = first("X")
        return (None, False, None) if x is None else \
            (6 * x.numel, False, None)

    if t == "layer_norm":
        x = first("X")
        return (None, False, None) if x is None else \
            (8 * x.numel, False, None)

    if t == "rms_norm":
        x = first("X")
        return (None, False, None) if x is None else \
            (4 * x.numel, False, None)

    if t == "rotary_embedding":
        x = first("X")
        return (None, False, None) if x is None else \
            (3 * x.numel, False, None)

    if t == "moe_router":
        x, w = first("X"), first("W")
        if x is None or w is None or len(w.shape) != 2:
            return None, False, None
        return 2 * x.numel * w.shape[1], True, None

    if t == "moe_experts":
        # three grouped products over the rows routed to HELD experts.
        # How many that is is run-time data: booked at its expectation
        # under uniform routing, tokens * top_k * held / total
        x, w = first("X"), first("WGate")
        if x is None or w is None or len(w.shape) != 2:
            return None, False, None
        tokens = x.numel // x.shape[-1]
        rows = (tokens * int(op.attrs["top_k"])
                * int(op.attrs["experts_held"])
                // int(op.attrs["experts_total"]))
        return (6 * rows * x.shape[-1] * w.shape[1], False,
                "routed rows at their expectation under uniform routing")

    if t == "scaled_dot_product_attention":
        # the attention op the models place (ops/nn_ops.py): two
        # seq^2 contractions plus the online softmax. Without this rule
        # the generic 1-flop/elem fallback would book ~Sq*d instead of
        # ~4*Sq*Sk*d and silently crater reported MFU.
        q, k, v = first("Q"), first("K"), first("V")
        if q is None or k is None or len(q.shape) < 3:
            return None, False, None
        # attr layout says where the rows are: [.., h, S, d] head-major,
        # [b, S, h, d] sequence-major ("bshd": never a cached decode)
        d = q.shape[-1]
        if op.attrs.get("layout", "bhsd") == "bshd" and len(q.shape) == 4:
            lead, sq, sk = q.shape[0] * q.shape[2], q.shape[1], k.shape[1]
        else:
            lead, sq, sk = _prod(q.shape[:-2]), q.shape[-2], k.shape[-2]
        d_v = v.shape[-1] if v is not None and v.shape else d
        pairs = sq * _attended_rows(op, sk)
        window = int(op.attrs.get("window", 0) or 0)
        if 0 < window < sq == sk:
            # a windowed site at its band (query i sees keys
            # i - window < j <= i), not at the score matrix
            pairs = window * (window + 1) // 2 + (sq - window) * window
        return (2 * lead * pairs * (d + d_v) + 5 * lead * pairs,
                True, None)

    if t == "ssd_prefill":
        # the chunked scan (ops/ssm_ops.py): inside a chunk the scores
        # C B^T and the masked product with X, between chunks what each
        # leaves its end and what the carried state gives each row
        x, b = first("X"), first("B")
        if x is None or b is None or len(x.shape) != 3:
            return None, False, None
        n, s, cols = x.shape
        q = min(int(op.attrs.get("chunk", 256)), s)
        d_state = b.shape[-1]
        return (2 * n * s * (q * d_state + q * cols + 2 * d_state * cols),
                True, None)

    if t == "ssm_state_update":
        # decay, the outer product's add, the contraction with C: five
        # operations an element of the state
        st = first("State")
        return (None, False, None) if st is None else \
            (5 * st.numel, True, None)

    if t == "gated_delta_prefill":
        # the chunked form (ops/delta_ops.py), a head a chunk of c rows:
        # K K^T and Q K^T, the unit-triangular solve of the d_v + d_k
        # right-hand columns, W S_0 and Q S_0, the masked product with
        # D, and what the chunk leaves its end
        q, v, a = first("Q"), first("V"), first("A")
        if q is None or v is None or a is None or len(q.shape) != 3:
            return None, False, None
        n, s, heads = a.shape
        d_k, d_v = q.shape[-1] // heads, v.shape[-1] // heads
        c = min(int(op.attrs.get("chunk", 64)), s)
        return (n * s * heads * (4 * c * d_k + c * (d_v + d_k)
                                 + 2 * c * d_v + 6 * d_k * d_v),
                True, None)

    if t == "gated_delta_state_update":
        # the decay, two reads (S^T k, S^T q), the outer product's
        # product and add: seven operations an element of the state
        st = first("State")
        return (None, False, None) if st is None else \
            (7 * st.numel, True, None)

    if t in ("causal_conv1d", "conv_state_update"):
        x, w = first("X"), first("W")
        if x is None or w is None:
            return None, False, None
        return 2 * w.shape[0] * x.numel, True, None

    if t in ("grouped_causal_conv1d", "grouped_conv_state_update"):
        # a [width, width] product a head a tap (ops/cca_ops.py): W is
        # [taps * heads * width, width]
        x, w = first("X"), first("W")
        if x is None or w is None or len(w.shape) != 2:
            return None, False, None
        return (2 * (x.numel // x.shape[-1]) * w.shape[0] * w.shape[1],
                True, None)

    if t == "cca_qk_mix":
        # the mean join, the squared norm and the scaling of each head
        z = first("Z")
        return (None, False, None) if z is None else \
            (10 * z.numel, False, None)

    if t == "mlp_router":
        # the down-projection, two hidden layers and the scores, then
        # the GELUs and the softmax
        x, wd, w3 = first("X"), first("WDown"), first("W3")
        if x is None or wd is None or w3 is None:
            return None, False, None
        rows, h = x.numel // x.shape[-1], wd.shape[1]
        return (2 * rows * (x.shape[-1] * h + 2 * h * h + h * w3.shape[1])
                + 20 * rows * h, True, None)

    if t in ("lstm", "gru"):
        # the fused recurrence mega-ops (ops/sequence_ops.py, Pallas
        # fused_lstm/fused_gru): the per-step recurrent matmul
        # [n,h]x[h,Gh] over all timesteps dominates; +12 flop/elem
        # covers the gate nonlinearities. The leading dims product is
        # n*t for a padded [n, t, Gh] input and the declared row count
        # for a ragged 2-D declaration (the padded time extent is not
        # statically known — same documented approximation as the
        # generic -1 binding).
        x, w = first("Input"), first("Weight")
        if x is None or w is None or len(x.shape) < 2 \
                or len(w.shape) != 2:
            return None, False, None
        nt = _prod(x.shape[:-1])
        h = w.shape[0]
        gates = 4 if t == "lstm" else 3
        return 2 * nt * h * gates * h + 12 * nt * h, True, None

    if t in _OPTIMIZER_FLOPS:
        p = first("Param")
        if p is None:
            return None, False, None
        return _OPTIMIZER_FLOPS[t] * p.numel, True, None

    if t in _SPARSE_OPTIMIZER_FLOPS:
        g = first("Grad")
        if g is None:
            return None, False, None
        return (_SPARSE_OPTIMIZER_FLOPS[t] * g.numel, True,
                "sparse apply: touched rows only")

    if t == "__vjp__":
        fwd_dict = op.attrs.get("fwd_op")
        if not fwd_dict:
            return None, False, None
        fwd = ir.OpDesc.from_dict(fwd_dict)
        f_flops, _f_exact, _ = _flops_for(fwd, lookup)
        if f_flops is None:
            # fall back on the forward op's output sizes
            f_flops = sum((lookup(n).numel if lookup(n) else 0)
                          for n in fwd.output_names())
        # backward ~= 2x forward (input-grad + weight-grad each pay one
        # forward-sized contraction for the matmul/conv family)
        return 2 * f_flops, False, f"vjp x2 of {fwd.type}"

    return None, False, None


def _bytes_override(op: ir.OpDesc,
                    lookup: Callable[[str], Optional[_VarInfo]]
                    ) -> Optional[Tuple[int, str]]:
    """Op types whose generic operand-bytes walk badly overcounts."""
    if op.type in ("lookup_table", "embedding_bag", "gather",
                   "gather_nd"):
        # a gather touches the SELECTED rows, not the whole table
        # (MULTICHIP_r05: model-axis gather traffic scales with touched
        # rows) — count ids + read of gathered rows + write of output
        touched = 0
        for names in op.outputs.values():
            for n in names:
                v = lookup(n)
                if v is not None:
                    touched += v.bytes
        ids = 0
        for slot in ("Ids", "Index"):
            v_names = op.input(slot)
            if v_names:
                v = lookup(v_names[0])
                if v is not None:
                    ids += v.bytes
        return 2 * touched + ids, "gather: touched rows only"
    if op.type in ("kv_cache_write", "kv_cache_append"):
        # an in-place dynamic-update-slice touches the UPDATED rows,
        # not the whole cache: counting the full [slots, h, max_seq, d]
        # cache as read+written per decoded token would overstate
        # decode-step traffic by max_seq/1 and crater reported
        # arithmetic intensity. The cache-READ traffic of attention is
        # booked on the consumer (scaled_dot_product_attention with a
        # KvLen, at its bound), not here.
        new_b = 0
        names = op.input("New")
        if names:
            v = lookup(names[0])
            if v is not None:
                new_b = v.bytes
        idx = 0
        for slot in ("Slot", "Pos"):
            v_names = op.input(slot)
            if v_names:
                v = lookup(v_names[0])
                if v is not None:
                    idx += v.bytes
        return 2 * new_b + idx, "kv cache: updated rows only"
    if op.type == "slot_state_write":
        # as kv_cache_write: the slot's rows, not the whole array
        total = 0
        for slot in ("New", "Slot"):
            v = lookup(op.input(slot)[0]) if op.input(slot) else None
            if v is not None:
                total += v.bytes * (2 if slot == "New" else 1)
        return total, "slot state: the written slot only"
    if op.type in ("sparse_sgd", "sparse_adagrad", "sparse_adam"):
        # sparse apply touches the DEDUPED rows only: the generic walk
        # would charge the full [vocab, dim] param (and each slot) as
        # read+written, overstating a billion-row table's update
        # traffic by vocab/touched. Real traffic per touched row:
        # param read+write + grad read (3x touched) plus a read+write
        # of every row-wise slot (adagrad: moment; adam: m1+m2), plus
        # the deduped ids. Scalar beta-pow accumulators are noise.
        touched = 0
        names = op.input("Grad")
        if names:
            v = lookup(names[0])
            if v is not None:
                touched = v.bytes
        n_slots = {"sparse_sgd": 0, "sparse_adagrad": 1,
                   "sparse_adam": 2}[op.type]
        ids = 0
        names = op.input("Ids")
        if names:
            v = lookup(names[0])
            if v is not None:
                ids = v.bytes
        return ((3 + 2 * n_slots) * touched + ids,
                "sparse apply: touched rows + slots only")
    if op.type == "scaled_dot_product_attention" and op.input("KvLen"):
        # cached decode: K and V are whole [slots, h, max_seq, d]
        # caches of which at most kv_bound rows a slot are read — the
        # cache read is booked here, at the bound (the slice that used
        # to carry it is gone), plus Q, the lengths and Out
        total = 0
        for slot in ("K", "V"):
            v = lookup(op.input(slot)[0])
            if v is None:
                return None
            total += v.bytes // v.shape[-2] * _attended_rows(
                op, v.shape[-2])
        for name in (op.input("Q") + op.input("KvLen")
                     + op.output("Out")):
            v = lookup(name)
            if v is not None:
                total += v.bytes
        return total, "cached attention: rows under the bound only"
    if op.type == "slice":
        # a slice reads exactly the rows it keeps, not the array it
        # cuts them from
        out_b = 0
        for names in op.outputs.values():
            for n in names:
                v = lookup(n)
                if v is not None:
                    out_b += v.bytes
        return 2 * out_b, "slice: kept rows only"
    return None


def _loop_body(op: ir.OpDesc):
    """(sub-block idx, the loop op) for a counted loop or its grad op
    (which embeds it), else None. A loop is an op whose trip count the
    program states: ``static_rnn``. A ``while`` runs as often as the
    run decides and is counted once, as before."""
    if op.type == "__vjp__":
        fwd = op.attrs.get("fwd_op") or {}
        if fwd.get("type") != "static_rnn":
            return None
        op = ir.OpDesc.from_dict(fwd)
    elif op.type != "static_rnn":
        return None
    idx = op.attrs.get("sub_block_idx")
    return (idx, op) if isinstance(idx, int) else None


def _loop_trips(op: ir.OpDesc, lookup) -> int:
    """Times a ``static_rnn`` runs its sub-block: attr ``steps`` (the
    counted form) or the leading dim of its first step input."""
    steps = op.attrs.get("steps")
    if steps:
        return int(steps)
    for name in op.input("X"):
        v = lookup(name)
        if v is not None and v.shape:
            return max(1, int(v.shape[0]))
    return 1


# ---------------------------------------------------------------------------
def program_cost(program, block_idx: int = 0,
                 feed_shapes: Optional[Dict[str, Sequence[int]]] = None,
                 batch: Optional[int] = None,
                 label: Optional[str] = None) -> ProgramCost:
    """Walk every reachable op of ``program`` (builder wrapper or core
    ``ir.Program``) and return its :class:`ProgramCost`.

    ``feed_shapes`` maps feed names to concrete shapes (the executor
    passes the current dispatch's device-feed shapes); a declared
    leading ``-1`` then resolves to the fed batch. Without feeds,
    ``batch`` (default 1) binds the dynamic batch dim. Non-leading
    dynamic dims bind to 1 (documented approximation — ragged padded
    time dims are not statically known).
    """
    desc = program.desc if hasattr(program, "desc") else program
    feed_shapes = {k: tuple(int(d) for d in v)
                   for k, v in (feed_shapes or {}).items()}
    root = desc.blocks[block_idx]
    if batch is None:
        batch = 1
        for name, shape in feed_shapes.items():
            v = root.find_var_recursive(name)
            if v is not None and v.shape and shape \
                    and len(v.shape) == len(shape) and v.shape[0] == -1:
                batch = int(shape[0])
                break
    batch = max(1, int(batch))

    param_reads: Dict[str, int] = {}
    op_costs: List[OpCost] = []
    trips: Dict[int, int] = {}        # block idx -> times it runs a step
    loop_grads: List[Tuple[OpCost, int]] = []   # (row, its loop's block)

    # one resolution cache per block, shared by every op in it: params
    # and activations are read by several ops (fwd, __vjp__, optimizer)
    # and the parent-chain walk is the expensive part
    block_caches: Dict[int, Dict[str, Optional[_VarInfo]]] = {}

    for blk, path, i, op in iter_ops(desc, block_idx):
        cache = block_caches.setdefault(id(blk), {})

        def lookup(name: str, _blk=blk, _cache=cache
                   ) -> Optional[_VarInfo]:
            if name in _cache:
                return _cache[name]
            v = _blk.find_var_recursive(name)
            info = None
            if v is not None:
                if name in feed_shapes:
                    shape = list(feed_shapes[name])
                elif v.shape is not None:
                    shape = [
                        (batch if j == 0 else 1)
                        if (not isinstance(d, int) or d == -1) else int(d)
                        for j, d in enumerate(v.shape)]
                else:
                    shape = None
                if shape is not None:
                    info = _VarInfo(
                        name, shape,
                        _ITEMSIZE.get(v.dtype or "float32", 4),
                        v.persistable)
            _cache[name] = info
            return info

        flops, exact, note = _flops_for(op, lookup)
        runs = trips.get(blk.idx, 1)
        body = _loop_body(op)
        if body is not None and op.type != "__vjp__":
            trips[body[0]] = runs * _loop_trips(body[1], lookup)
        in_infos = [lookup(n) for n in dict.fromkeys(op.input_names())]
        out_infos = [lookup(n) for n in dict.fromkeys(op.output_names())]
        if flops is None:
            # generic estimate: one FLOP per output element
            resolved_out = [v for v in out_infos if v is not None]
            if resolved_out:
                flops, exact, note = (
                    sum(v.numel for v in resolved_out), False, "generic")
            else:
                flops, exact, note = 0, False, "unresolved shapes"

        ov = _bytes_override(op, lookup)
        if ov is not None:
            bytes_acc, bnote = ov
            note = note or bnote
        else:
            bytes_acc = sum(v.bytes for v in in_infos if v is not None) \
                + sum(v.bytes for v in out_infos if v is not None)
        pbytes = 0
        for v in in_infos:
            if v is not None and v.persistable:
                pbytes += v.bytes
                param_reads.setdefault(v.name, v.bytes)
        if runs != 1:
            flops, bytes_acc = flops * runs, bytes_acc * runs
            note = f"{note}; x{runs} trips" if note else f"x{runs} trips"
        op_costs.append(OpCost(op.type, path, i, flops, bytes_acc,
                               pbytes, exact, note))
        if op.type == "__vjp__" and body is not None:
            loop_grads.append((op_costs[-1], body[0]))

    # a loop's grad op: twice its body's work over all the trips (the
    # body's rows come after the parent block's, so only now)
    for row, sub_idx in loop_grads:
        inside = [c for c in op_costs if sub_idx in c.block_path[1:]]
        row.flops = 2 * sum(c.flops for c in inside)
        row.bytes_accessed = 2 * sum(c.bytes_accessed for c in inside)
        row.note = "vjp x2 of the loop's body over its trips"

    return ProgramCost(op_costs, sum(param_reads.values()), batch,
                       block_idx,
                       label=label or f"program uid={desc.uid}")


# ---------------------------------------------------------------------------
@register_pass
class CostModelPass(AnalysisPass):
    """Attach a :class:`ProgramCost` to the verify report
    (``report.cost``). Produces no diagnostics — it is an attribution
    pass on the same framework, runnable alongside the verifier
    (``ProgramVerifier(passes=[..., "cost_model"])``) or standalone via
    :func:`program_cost`."""

    name = "cost_model"

    def __init__(self, feed_shapes=None, batch=None):
        self.feed_shapes = feed_shapes
        self.batch = batch

    def run(self, ctx: PassContext) -> None:
        ctx.report.cost = program_cost(
            ctx.program, ctx.block_idx, feed_shapes=self.feed_shapes,
            batch=self.batch, label=ctx.report.program_label)
