"""Hybrid state-space / attention language model for the token server
(the block stack of IBM Granite 4.0-H, ``model_type``
``granitemoehybrid`` with no experts: Mamba-2 mixers, arXiv:2405.21060,
beside a few grouped-query attention layers with no position term).

``layer_types`` names each layer's mixer, "mamba" or "attention"; every
layer is ``x += r Mixer(RMS(x)); x += r FFN(RMS(x))`` with the gated
``intermediate_size`` FFN of models/decoder_moe.py and the published
multipliers: the embedding times ``embedding_multiplier``, each branch
times ``residual_multiplier`` r, attention scores times
``attention_multiplier`` (the fused op scales by 1 / sqrt(head_dim), so
q is multiplied by the quotient of the two), logits over
``logits_scaling`` through the TIED embedding.

A Mamba-2 mixer: ``[z | xBC | dt] = W_in u``; ``xBC = silu(conv(xBC))``
(depthwise, causal, ``mamba_d_conv`` taps, with bias); ``[x | B | C] =
xBC``; the recurrence of ops/ssm_ops.py over ``mamba_n_heads`` heads of
``mamba_d_head`` with a ``mamba_d_state``-wide state, B and C shared by
the heads (one group); ``g = y silu(z)``; ``W_out(RMS(g))`` with the
mean over all the heads' columns.

``build_hybrid_lm`` returns what models/transformer.py build_decoder_lm
returns — prefill programs by prompt bucket, decode programs by cache
bucket, full programs, one parameter set shared by name — and a slot
owns THREE kinds of persistable state: the attention layers' KV caches
(``kv_cache.*``), each Mamba layer's convolution window
(``conv_state.*``) and recurrent state (``ssm_state.*``). A prefill
into a slot overwrites all three; nothing is inherited from the request
that held the slot before.

Storage is by kind (``dtypes``): weights, KV and convolution state at
``weights`` / ``kv`` / ``conv`` (bfloat16 as served), the recurrent
state, norm scales, A_log, dt_bias and D float32. There is no float32
master copy of anything.
"""
from __future__ import annotations

import math

import numpy as np

from .. import layers
from ..initializer import (NormalInitializer, NumpyArrayInitializer,
                           UniformInitializer)
from ..layer_helper import LayerHelper, ParamAttr
from .decoder_moe import _embed, _heads, _linear, gated_ffn
from .served_lm import (create_states, last_real_rows, mode_feeds,
                        program_set, rms as _rms, tied_head)
from .transformer import (KV_CACHE_PREFIX, LMProgram, _cache_update,
                          _sdpa_op)

CONV_STATE_PREFIX = "conv_state."
SSM_STATE_PREFIX = "ssm_state."
STATE_PREFIXES = (KV_CACHE_PREFIX, CONV_STATE_PREFIX, SSM_STATE_PREFIX)

#: what is stored at what width, by kind
SERVED_DTYPES = {"weights": "bfloat16", "kv": "bfloat16",
                 "conv": "bfloat16", "ssm": "float32",
                 "scales": "float32"}

# the published keys the builder reads (every one is required)
ARCH_KEYS = (
    "hidden_size", "intermediate_size", "layer_types",
    "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
    "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_chunk_size",
    "mamba_n_groups", "embedding_multiplier", "residual_multiplier",
    "attention_multiplier", "logits_scaling", "rms_norm_eps")


def state_names(layer_types) -> dict:
    """{kind: [persistable state names]} of a stack: K and V a
    attention layer, one window and one recurrent state a mamba
    layer."""
    out = {"kv": [], "conv": [], "ssm": []}
    for i, kind in enumerate(layer_types):
        if kind == "attention":
            out["kv"] += [f"{KV_CACHE_PREFIX}l{i}.k",
                          f"{KV_CACHE_PREFIX}l{i}.v"]
        else:
            out["conv"].append(f"{CONV_STATE_PREFIX}l{i}")
            out["ssm"].append(f"{SSM_STATE_PREFIX}l{i}")
    return out


def _check(arch):
    missing = [k for k in ARCH_KEYS if arch.get(k) is None]
    if missing:
        raise ValueError(f"hybrid_ssm: the architecture lacks {missing}")
    odd = sorted(set(arch["layer_types"]) - {"mamba", "attention"})
    if odd:
        raise ValueError(f"hybrid_ssm: layer_types holds {odd}; a layer "
                         "is 'mamba' or 'attention'")
    if int(arch["mamba_n_groups"]) != 1:
        raise ValueError("hybrid_ssm builds one group of B and C for all "
                         "heads (mamba_n_groups 1)")
    if arch["num_attention_heads"] % arch["num_key_value_heads"] or \
            arch["hidden_size"] % arch["num_attention_heads"]:
        raise ValueError("hybrid_ssm: hidden_size, num_attention_heads "
                         "and num_key_value_heads do not divide")


def _sizes(arch):
    d_inner = arch["mamba_n_heads"] * arch["mamba_d_head"]
    return dict(
        d_inner=d_inner,
        conv_dim=d_inner + 2 * arch["mamba_d_state"],
        head_dim=arch["hidden_size"] // arch["num_attention_heads"])


def _mamba_parameters(arch, layer, seed, dtype):
    """The mixer's small parameters, made by every program alike (the
    projections come from ``_linear`` where they are used): the
    convolution's taps and bias at the weights' width; A_log = log
    U(1, 16), dt_bias the inverse softplus of a log-uniform step in
    [1e-3, 1e-1] and D = 1, float32."""
    heads, taps = arch["mamba_n_heads"], arch["mamba_d_conv"]
    conv_dim = _sizes(arch)["conv_dim"]
    helper = LayerHelper("mamba")
    bound = 1.0 / math.sqrt(taps)
    uniform = ParamAttr(initializer=UniformInitializer(-bound, bound))
    w = helper.create_parameter(uniform, [taps, conv_dim], dtype)
    bias = helper.create_parameter(uniform, [conv_dim], dtype,
                                   is_bias=True)
    rng = np.random.default_rng([int(seed), int(layer)])
    step = np.exp(rng.uniform(math.log(1e-3), math.log(1e-1), heads))

    def fixed(values):
        return helper.create_parameter(
            ParamAttr(initializer=NumpyArrayInitializer(
                np.asarray(values, np.float32))), [heads], "float32")

    a_log = fixed(np.log(rng.uniform(1.0, 16.0, heads)))
    dt_bias = fixed(step + np.log(-np.expm1(-step)))
    d = fixed(np.ones(heads))
    return w, bias, a_log, dt_bias, d


def _state_shapes(arch, slots, max_seq_len, dtypes):
    """{name: (shape, dtype)} of every persistable state of the stack,
    by kind."""
    size = _sizes(arch)
    shapes = {
        "kv": ([slots, arch["num_key_value_heads"], max_seq_len,
                size["head_dim"]], dtypes["kv"]),
        "conv": ([slots, (arch["mamba_d_conv"] - 1) * size["conv_dim"]],
                 dtypes["conv"]),
        "ssm": ([slots, arch["mamba_d_state"], size["d_inner"]],
                dtypes["ssm"])}
    return {name: shapes[kind]
            for kind, names in state_names(arch["layer_types"]).items()
            for name in names}


def _build_program(mode, seq_len, arch, vocab_size, max_seq_len, slots,
                   seed, dtypes, embedding_std):
    """One (main, startup) pair for ``mode`` at bucket ``seq_len`` (a
    prompt bucket for full / prefill, a cache bucket for decode). The
    parameter-creating calls run in ONE order in every mode: the names
    line up and every program reads the same scope arrays."""
    import paddle_tpu as pt
    from .. import framework
    size, eps = _sizes(arch), float(arch["rms_norm_eps"])
    d, w_dtype = arch["hidden_size"], dtypes["weights"]
    n_head, n_kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    residual = float(arch["residual_multiplier"])
    # the fused op divides by sqrt(head_dim); the model multiplies by
    # attention_multiplier
    q_scale = float(arch["attention_multiplier"]) \
        * math.sqrt(size["head_dim"])
    cfg = {"hidden_size": d, "init_depth": None}
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed
    with pt.program_guard(main, startup), framework.isolated_name_scope():
        decode = mode == "decode"
        ids, positions, lengths, slot, feeds = mode_feeds(mode, seq_len,
                                                          slots)
        states = create_states(_state_shapes(
            arch, slots, max_seq_len, dtypes)) if mode != "full" else {}

        helper = LayerHelper("hybrid_lm")
        table = helper.create_parameter(
            ParamAttr(initializer=NormalInitializer(0.0, embedding_std)),
            [vocab_size, d], w_dtype)
        x = layers.scale(_embed(table, ids),
                         scale=float(arch["embedding_multiplier"]))

        def attention(i, u):
            q = layers.scale(
                _heads(_linear(u, n_head * size["head_dim"], "attn_q"),
                       n_head, size["head_dim"]), scale=q_scale)
            k = _heads(_linear(u, n_kv * size["head_dim"], "attn_k"),
                       n_kv, size["head_dim"])
            v = _heads(_linear(u, n_kv * size["head_dim"], "attn_v"),
                       n_kv, size["head_dim"])
            if decode:
                kc = states[f"{KV_CACHE_PREFIX}l{i}.k"]
                vc = states[f"{KV_CACHE_PREFIX}l{i}.v"]
                _cache_update("kv_cache_append", kc, k, positions, "Pos")
                _cache_update("kv_cache_append", vc, v, positions, "Pos")
                ctx = _sdpa_op(q, kc, vc, None, causal=False,
                               kv_len=lengths, kv_bound=seq_len)
            else:
                if mode == "prefill":
                    for kind, new in (("k", k), ("v", v)):
                        _cache_update(
                            "kv_cache_write",
                            states[f"{KV_CACHE_PREFIX}l{i}.{kind}"], new,
                            slot, "Slot")
                # rows beyond a prompt's length are LATER rows: the
                # causal attr alone hides them from the real ones
                ctx = _sdpa_op(q, k, v, None, causal=True)
            merged = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                                    [0, 0, n_head * size["head_dim"]])
            return _linear(merged, d, "attn_o")

        def mamba(i, u):
            w, bias, a_log, dt_bias, skip = _mamba_parameters(
                arch, i, seed, w_dtype)
            z, xbc, dt = layers.split(
                _linear(u, 2 * size["d_inner"] + 2 * arch["mamba_d_state"]
                        + arch["mamba_n_heads"], "mamba_in"),
                [size["d_inner"], size["conv_dim"], arch["mamba_n_heads"]],
                dim=2)
            if decode:
                xbc = layers.conv_state_update(
                    states[f"{CONV_STATE_PREFIX}l{i}"], xbc, w, bias)
            else:
                xbc, window = layers.causal_conv1d(xbc, w, bias, lengths)
                if mode == "prefill":
                    layers.slot_state_write(
                        states[f"{CONV_STATE_PREFIX}l{i}"], window, slot)
            xs, b, c = layers.split(
                layers.swish(xbc),
                [size["d_inner"], arch["mamba_d_state"],
                 arch["mamba_d_state"]], dim=2)
            if decode:
                y = layers.ssm_state_update(
                    states[f"{SSM_STATE_PREFIX}l{i}"], xs, dt, b, c,
                    a_log, dt_bias, skip)
            else:
                y, final = layers.ssd_prefill(
                    xs, dt, b, c, a_log, dt_bias, skip, lengths,
                    chunk=arch["mamba_chunk_size"])
                if mode == "prefill":
                    layers.slot_state_write(
                        states[f"{SSM_STATE_PREFIX}l{i}"], final, slot)
            gated = layers.elementwise_mul(y, layers.swish(z))
            return _linear(_rms(gated, eps, "mamba_norm"), d, "mamba_out")

        for i, kind in enumerate(arch["layer_types"]):
            u = _rms(x, eps)
            mixed = attention(i, u) if kind == "attention" else mamba(i, u)
            x = layers.elementwise_add(x, layers.scale(mixed,
                                                       scale=residual))
            f = gated_ffn(_rms(x, eps), arch["intermediate_size"], cfg,
                          "ffn")
            x = layers.elementwise_add(x, layers.scale(f, scale=residual))

        if not decode:
            x = last_real_rows(x, lengths, seq_len, w_dtype)   # [n, 1, d]
        logits = layers.scale(
            tied_head(x, table, eps),
            scale=1.0 / float(arch["logits_scaling"]))     # [n, 1, V]
        next_tok = layers.argmax(logits, axis=-1)          # [n, 1]
    return LMProgram(main, startup, feeds, next_tok.name)


def build_hybrid_lm(arch, vocab_size=1000, max_seq_len=64, slots=4,
                    prompt_buckets=(16, 32, 64), cache_buckets=(32, 64),
                    seed=0, dtypes=None, embedding_std=0.02):
    """The generation program set of one hybrid stack, shaped as
    models/transformer.py build_decoder_lm's: {"prefill": {S:
    LMProgram}, "decode": {L: LMProgram}, "full": {S: LMProgram},
    "startup": Program, "cache_names": [...], "state_kinds": {kind:
    [names]}, "state_prefixes": (...)}. ``arch`` holds
    the published config keys (ARCH_KEYS), ``dtypes`` the storage
    width by kind (SERVED_DTYPES where None). The "full" programs are
    built when first asked for (served_lm.py ``OnAsk``)."""
    _check(arch)
    dtypes = dict(SERVED_DTYPES, **(dtypes or {}))
    args = (arch, vocab_size, max_seq_len, slots, seed, dtypes,
            float(embedding_std))
    return program_set(
        lambda mode, bucket: _build_program(mode, bucket, *args),
        max_seq_len, prompt_buckets, cache_buckets,
        state_names(arch["layer_types"]), STATE_PREFIXES)
