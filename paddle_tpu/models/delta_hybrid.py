"""Hybrid linear-attention / attention language model for the token
server (the block stack of AllenAI Olmo-Hybrid, ``model_type``
``olmo_hybrid``: gated delta-rule layers, Gated DeltaNet
arXiv:2412.06464 with arXiv:2411.12537's negative eigenvalues, beside
full multi-head attention layers with a norm on the whole query and key
projections and no position term).

``layer_types`` names each layer's mixer, "linear_attention" or
"full_attention". A block has OLMo 2's reordered norm (arXiv:2501.00656):
``h = x + RMS(Mixer(x))``, ``x' = h + RMS(W_down(silu(W_gate h) * W_up
h))`` — the norm is on each branch's OUTPUT and the branch reads the
stream as it is; after the last layer a final RMS and a head of its own
(``tie_word_embeddings`` false), float32 logits; the embedding enters
unscaled; no bias anywhere.

A linear-attention layer: ``q~ = W_q u``, ``k~ = W_k u``, ``v~ = W_v u``,
each through ITS OWN depthwise causal convolution of
``linear_conv_kernel_dim`` taps (no bias) and SiLU; ``z = W_g u``, ``a =
W_a u``, ``b = W_b u`` (one a head); the recurrence of ops/delta_ops.py
over ``linear_num_value_heads`` heads with a ``linear_key_head_dim`` x
``linear_value_head_dim`` state; ``o_h = RMS(o_h; one scale of
linear_value_head_dim for all heads) * silu(z_h)``; ``W_o``.

A full-attention layer: ``q = RMS(W_q u)``, ``k = RMS(W_k u)`` (over the
whole projection, before the head split), ``v = W_v u``; causal softmax
attention at ``head_dim ** -0.5`` over ``num_key_value_heads`` key heads;
``W_o``.

``build_delta_hybrid_lm`` returns what models/transformer.py
build_decoder_lm returns (models/served_lm.py ``program_set``), and a
slot owns THREE kinds of persistable state: the attention layers' KV
caches (``kv_cache.*``), each linear layer's three convolution windows
(``conv_state.l<i>.q`` / ``.k`` / ``.v``) and its matrix state
(``delta_state.*``, ``[slots, d_k, heads * d_v]``). A prefill into a
slot overwrites every one of them; nothing is inherited from the
request that held the slot before.

Storage is by kind (``dtypes``): weights, taps, KV and windows at
``weights`` / ``kv`` / ``conv`` (bfloat16 as served), the matrix state at
``delta``, A_log, dt_bias and every norm scale float32. There is no
float32 master copy of anything.
"""
from __future__ import annotations

import math

import numpy as np

from .. import layers
from ..initializer import NormalInitializer, NumpyArrayInitializer, \
    UniformInitializer
from ..layer_helper import LayerHelper, ParamAttr
from .decoder_moe import _embed, _heads, _linear, gated_ffn
from .served_lm import (create_states, last_real_rows, mode_feeds,
                        program_set, rms, untied_head)
from .transformer import (KV_CACHE_PREFIX, LMProgram, _cache_update,
                          _sdpa_op)

CONV_STATE_PREFIX = "conv_state."
DELTA_STATE_PREFIX = "delta_state."
STATE_PREFIXES = (KV_CACHE_PREFIX, CONV_STATE_PREFIX, DELTA_STATE_PREFIX)
#: the three windows a linear layer carries
WINDOWS = ("q", "k", "v")
#: positions a chunk of the prefill's chunked form holds
CHUNK = 64

#: what is stored at what width, by kind
SERVED_DTYPES = {"weights": "bfloat16", "kv": "bfloat16",
                 "conv": "bfloat16", "delta": "float32",
                 "scales": "float32"}

# the published keys the builder reads (every one is required)
ARCH_KEYS = (
    "hidden_size", "intermediate_size", "layer_types",
    "num_attention_heads", "num_key_value_heads", "linear_num_key_heads",
    "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim",
    "linear_allow_neg_eigval", "rms_norm_eps")


def state_names(layer_types) -> dict:
    """{kind: [persistable state names]} of a stack: K and V a full
    layer, three windows and one matrix state a linear layer."""
    out = {"kv": [], "conv": [], "delta": []}
    for i, kind in enumerate(layer_types):
        if kind == "full_attention":
            out["kv"] += [f"{KV_CACHE_PREFIX}l{i}.k",
                          f"{KV_CACHE_PREFIX}l{i}.v"]
        else:
            out["conv"] += [f"{CONV_STATE_PREFIX}l{i}.{w}"
                            for w in WINDOWS]
            out["delta"].append(f"{DELTA_STATE_PREFIX}l{i}")
    return out


def _check(arch):
    missing = [k for k in ARCH_KEYS if arch.get(k) is None]
    if missing:
        raise ValueError(f"delta_hybrid: the architecture lacks {missing}")
    odd = sorted(set(arch["layer_types"])
                 - {"linear_attention", "full_attention"})
    if odd:
        raise ValueError(f"delta_hybrid: layer_types holds {odd}; a "
                         "layer is 'linear_attention' or 'full_attention'")
    if arch["linear_num_key_heads"] != arch["linear_num_value_heads"]:
        raise ValueError("delta_hybrid builds one key head a value head "
                         "(linear_num_key_heads == "
                         "linear_num_value_heads)")
    if arch["num_attention_heads"] % arch["num_key_value_heads"] or \
            arch["hidden_size"] % arch["num_attention_heads"]:
        raise ValueError("delta_hybrid: hidden_size, num_attention_heads "
                         "and num_key_value_heads do not divide")


def _sizes(arch):
    heads = arch["linear_num_value_heads"]
    return dict(
        heads=heads, key=heads * arch["linear_key_head_dim"],
        value=heads * arch["linear_value_head_dim"],
        head_dim=arch["hidden_size"] // arch["num_attention_heads"])


def _state_shapes(arch, slots, max_seq_len, dtypes):
    """{name: (shape, dtype)} of every persistable state of the stack."""
    size = _sizes(arch)
    rows = arch["linear_conv_kernel_dim"] - 1
    cache = ([slots, arch["num_key_value_heads"], max_seq_len,
              size["head_dim"]], dtypes["kv"])
    columns = {"q": size["key"], "k": size["key"], "v": size["value"]}
    names = state_names(arch["layer_types"])
    shapes = {name: cache for name in names["kv"]}
    shapes.update({
        name: ([slots, rows * columns[name.rsplit(".", 1)[1]]],
               dtypes["conv"]) for name in names["conv"]})
    shapes.update({
        name: ([slots, arch["linear_key_head_dim"], size["value"]],
               dtypes["delta"]) for name in names["delta"]})
    return shapes


def _gate_parameters(arch, layer, seed):
    """(A_log, dt_bias) [heads] float32 of one linear layer, made by
    every program alike: A_log = log U(1, 16) and dt_bias the inverse
    softplus of a log-uniform step in [1e-3, 1e-1] (Mamba-2's own, which
    the Gated DeltaNet release keeps): alpha = exp(-A dt) spans heads
    that forget in two steps and heads that keep a thousand."""
    heads = arch["linear_num_value_heads"]
    helper = LayerHelper("delta_gate")
    rng = np.random.default_rng([int(seed), int(layer)])
    step = np.exp(rng.uniform(math.log(1e-3), math.log(1e-1), heads))

    def fixed(values):
        return helper.create_parameter(
            ParamAttr(initializer=NumpyArrayInitializer(
                np.asarray(values, np.float32))), [heads], "float32")

    return (fixed(np.log(rng.uniform(1.0, 16.0, heads))),
            fixed(step + np.log(-np.expm1(-step))))


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
    taps = arch["linear_conv_kernel_dim"]
    beta_scale = 2.0 if arch["linear_allow_neg_eigval"] else 1.0
    cfg = {"hidden_size": d, "init_depth": None}
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed
    with pt.program_guard(main, startup), framework.isolated_name_scope():
        decode = mode == "decode"
        ids, positions, lengths, slot, feeds = mode_feeds(mode, seq_len,
                                                          slots)
        states = create_states(_state_shapes(
            arch, slots, max_seq_len, dtypes)) if mode != "full" else {}

        helper = LayerHelper("delta_hybrid_lm")
        table = helper.create_parameter(
            ParamAttr(initializer=NormalInitializer(0.0, embedding_std)),
            [vocab_size, d], w_dtype)
        x = _embed(table, ids)

        def attention(i, u):
            q = _heads(rms(_linear(u, n_head * size["head_dim"], "attn_q"),
                           eps, "q_norm"), n_head, size["head_dim"])
            k = _heads(rms(_linear(u, n_kv * size["head_dim"], "attn_k"),
                           eps, "k_norm"), n_kv, size["head_dim"])
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

        def convolved(i, which, t):
            """silu(conv(t)): a prompt with its lengths (a prefill
            writes the window the last real token left into the slot),
            or one token a slot against the window. The taps are U(-b,
            b), b = 1 / sqrt(taps); the shared op's bias is a zero
            constant of the program, no parameter."""
            width = int(t.shape[-1])
            bound = 1.0 / math.sqrt(taps)
            w = LayerHelper("delta_conv").create_parameter(
                ParamAttr(initializer=UniformInitializer(-bound, bound)),
                [taps, width], w_dtype)
            no_bias = layers.fill_constant([width], w_dtype, 0.0)
            window = states.get(f"{CONV_STATE_PREFIX}l{i}.{which}")
            if decode:
                out = layers.conv_state_update(window, t, w, no_bias)
            else:
                out, last = layers.causal_conv1d(t, w, no_bias, lengths)
                if mode == "prefill":
                    layers.slot_state_write(window, last, slot)
            return layers.swish(out)

        def linear_attention(i, u):
            q = convolved(i, "q", _linear(u, size["key"], "delta_q"))
            k = convolved(i, "k", _linear(u, size["key"], "delta_k"))
            v = convolved(i, "v", _linear(u, size["value"], "delta_v"))
            a = _linear(u, size["heads"], "delta_a")
            b = _linear(u, size["heads"], "delta_b")
            a_log, dt_bias = _gate_parameters(arch, i, seed)
            z = _linear(u, size["value"], "delta_g")
            if decode:
                o = layers.gated_delta_state_update(
                    states[f"{DELTA_STATE_PREFIX}l{i}"], q, k, v, a, b,
                    a_log, dt_bias, beta_scale=beta_scale)
            else:
                o, final = layers.gated_delta_prefill(
                    q, k, v, a, b, a_log, dt_bias, lengths, chunk=CHUNK,
                    beta_scale=beta_scale)
                if mode == "prefill":
                    layers.slot_state_write(
                        states[f"{DELTA_STATE_PREFIX}l{i}"], final, slot)
            by_head = [0, 0, size["heads"], arch["linear_value_head_dim"]]
            gated = layers.elementwise_mul(
                rms(layers.reshape(o, by_head), eps, "delta_norm"),
                layers.swish(layers.reshape(z, by_head)))
            return _linear(layers.reshape(gated, [0, 0, size["value"]]),
                           d, "delta_o")

        for i, kind in enumerate(arch["layer_types"]):
            mixer = attention if kind == "full_attention" \
                else linear_attention
            x = layers.elementwise_add(x, rms(mixer(i, x), eps))
            x = layers.elementwise_add(x, rms(gated_ffn(
                x, arch["intermediate_size"], cfg, "ffn"), eps))

        if not decode:
            x = last_real_rows(x, lengths, seq_len, w_dtype)   # [n, 1, d]
        logits = untied_head(x, vocab_size, eps, w_dtype)   # [n, 1, V]
        next_tok = layers.argmax(logits, axis=-1)            # [n, 1]
    return LMProgram(main, startup, feeds, next_tok.name)


def build_delta_hybrid_lm(arch, vocab_size=1000, max_seq_len=64, slots=4,
                          prompt_buckets=(16, 32, 64),
                          cache_buckets=(32, 64), seed=0, dtypes=None,
                          embedding_std=1.0):
    """The generation program set of one delta-rule / attention stack
    (models/served_lm.py ``program_set``). ``arch`` holds the published
    config keys (ARCH_KEYS), ``dtypes`` the storage width by kind
    (SERVED_DTYPES where None)."""
    _check(arch)
    dtypes = dict(SERVED_DTYPES, **(dtypes or {}))
    args = (arch, vocab_size, max_seq_len, slots, seed, dtypes,
            float(embedding_std))
    return program_set(
        lambda mode, bucket: _build_program(mode, bucket, *args),
        max_seq_len, prompt_buckets, cache_buckets,
        state_names(arch["layer_types"]), STATE_PREFIXES)
