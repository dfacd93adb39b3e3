"""What the token server's program families with more than a KV cache a
slot share (models/hybrid_ssm.py, models/cca_moe.py,
models/delta_hybrid.py): the three modes' feeds, the creation of a
slot's persistable state, the float32-scaled RMS norm, the row of a
prompt the head reads, the float32 head (tied or its own), the
re-forward programs built when first asked for, and the assembly of the
program set that serving/generation/model.py GenerationModel takes —
shaped as models/transformer.py build_decoder_lm's.

The helpers append the ops a family's builder appended itself before
they were lifted here, in the same order and under the same layer names:
a family's programs serialise to the same bytes (tests/test_cca_moe.py
holds hybrid_ssm's).
"""
from __future__ import annotations

from .. import layers
from ..initializer import ConstantInitializer, UniformInitializer
from ..layer_helper import LayerHelper, ParamAttr


def mode_feeds(mode, seq_len, slots):
    """The data layers of one program: (token ids, positions or None,
    lengths, slot or None, the feed names). ``prefill`` reads one
    request [1, S, 1] into the fed ``slot``; ``decode`` one token a slot
    [slots, 1, 1] at the fed ``positions``; ``full`` a padded [slots, S,
    1] matrix. ``lengths`` says how many rows of each are real (a decode
    step's: how many cache rows are live, 0 for an empty slot)."""
    decode = mode == "decode"
    n = 1 if mode == "prefill" else slots
    ids = layers.data("token_ids", [n, 1 if decode else seq_len, 1],
                      dtype="int64", append_batch_size=False)
    feeds = ["token_ids"]
    slot = positions = None
    if decode:
        positions = layers.data("positions", [slots], dtype="int64",
                                append_batch_size=False)
        feeds.append("positions")
    lengths = layers.data("lengths", [n], dtype="int64",
                          append_batch_size=False)
    feeds.append("lengths")
    if mode == "prefill":
        slot = layers.data("slot", [1], dtype="int64",
                           append_batch_size=False)
        feeds.append("slot")
    return ids, positions, lengths, slot, feeds


def create_states(shapes):
    """{name: var}: the persistable per-slot state ``shapes`` ({name:
    (shape, dtype)}, in creation order) describes, zero-filled by the
    startup program."""
    helper = LayerHelper("slot_state")
    out = {}
    for name, (shape, dtype) in shapes.items():
        v = helper.create_global_variable(shape, dtype, name=name,
                                          persistable=True)
        helper.set_variable_initializer(v, ConstantInitializer(0.0))
        out[name] = v
    return out


def rms(x, eps, name="norm"):
    """layers.rms_norm with a float32 scale whatever x's width."""
    helper = LayerHelper(name)
    scale = helper.create_parameter(
        None, [int(x.shape[-1])], "float32",
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="rms_norm", inputs={"X": x, "Scale": scale},
                     outputs={"Y": out}, attrs={"epsilon": eps})
    return out


def last_real_rows(x, lengths, seq_len, dtype):
    """x [n, S, d] -> [n, 1, d]: each row's last real position, before
    the head: one row of logits a request, not one a position."""
    one = layers.fill_constant([1], "int64", 1)
    last = layers.elementwise_sub(layers.unsqueeze(lengths, [1]), one)
    pick = layers.cast(layers.one_hot(last, seq_len), dtype)
    return layers.unsqueeze(layers.reduce_sum(
        layers.elementwise_mul(x, layers.unsqueeze(pick, [2])),
        dim=1), [1])


def tied_head(x, table, eps):
    """[n, 1, V] float32 logits of x [n, 1, d] through the final norm
    and the TIED embedding table [V, d]."""
    return layers.matmul(rms(x, eps, "final_norm"), table,
                         transpose_y=True, out_dtype="float32")


def untied_head(x, vocab_size, eps, dtype):
    """[n, 1, V] float32 logits of x [n, 1, d] through the final norm
    and a head of its own, [d, V] at the weights' width (made here: the
    last parameter of a stack)."""
    normed = rms(x, eps, "final_norm")
    helper = LayerHelper("lm_head")
    d = int(x.shape[-1])
    limit = (6.0 / (d + vocab_size)) ** 0.5        # Xavier-uniform
    head = helper.create_parameter(
        ParamAttr(initializer=UniformInitializer(-limit, limit)),
        [d, vocab_size], dtype)
    return layers.matmul(normed, head, out_dtype="float32")


class OnAsk(dict):
    """{bucket: LMProgram} whose programs are built when first asked
    for. The served path (mode "cached") never runs a re-forward
    program, and building and verifying one of 40 layers costs what a
    prefill program's costs (8 s a bucket of a cell's set-up); the
    names line up whenever it is built (``isolated_name_scope``).
    ``items()`` and ``in`` see what has been built."""

    def __init__(self, buckets, build):
        super().__init__()
        self._buckets, self._build = tuple(buckets), build

    def __missing__(self, bucket):
        if bucket not in self._buckets:
            raise KeyError(bucket)
        lm = self[bucket] = self._build(bucket)
        return lm


def program_set(build, max_seq_len, prompt_buckets, cache_buckets,
                state_kinds, state_prefixes):
    """The generation program set of one stack: {"prefill": {S:
    LMProgram}, "decode": {L: LMProgram}, "full": {S: LMProgram} (built
    when first asked for), "startup": Program, "cache_names": [...],
    "state_kinds": {kind: [names]}, "state_prefixes": (...)}.
    ``build(mode, bucket)`` makes one program."""
    prompt_buckets = sorted(set(int(s) for s in prompt_buckets))
    cache_buckets = sorted(set(int(c) for c in cache_buckets))
    if prompt_buckets[-1] > max_seq_len or cache_buckets[-1] > max_seq_len:
        raise ValueError(
            f"bucket exceeds max_seq_len={max_seq_len}: prompt "
            f"{prompt_buckets}, cache {cache_buckets}")
    out = {"prefill": {}, "decode": {}, "full": OnAsk(
        prompt_buckets, lambda s: build("full", s))}
    for s in prompt_buckets:
        out["prefill"][s] = build("prefill", s)
    for c in cache_buckets:
        out["decode"][c] = build("decode", c)
    out["startup"] = out["prefill"][prompt_buckets[0]].startup
    out["state_kinds"] = state_kinds
    out["cache_names"] = [n for names in state_kinds.values()
                          for n in names]
    out["state_prefixes"] = tuple(state_prefixes)
    return out
