"""Compressed-convolutional-attention / mixture-of-experts language model
for the token server (the block stack of Zyphra ZAYA1, ``model_type``
``zaya``: CCA, arXiv:2510.04476, beside top-1 experts behind an MLP
router, arXiv:2511.17127).

Every layer is ``x += CCA(RMS(x)); x += MoE(RMS(x))``; the logits are
``E^T RMS(x)`` through the TIED embedding, float32, no bias anywhere.

*CCA* (u the sublayer's normed input, d_h = ``head_dim``, h query heads
over c key heads): ``q~ = W_q u`` (h d_h), ``k~ = W_k u`` (c d_h), ``v =
[W_v1 u_t ; W_v2 u_(t-1)]`` (the first half of the value heads from this
token, the second from the one before). ``z = [q~ ; k~]`` passes a
depthwise causal convolution of ``cca_time0`` taps and one grouped by
head of ``cca_time1`` taps (ops/cca_ops.py), is joined with its
pre-convolution mean and L2-normalised head by head (``cca_qk_mix``), the
first ``partial_rotary_factor`` of every head is turned by the rotary
embedding (rotate-half) at the token's position, and attention runs in
that latent, grouped-query, causal. What is cached is what attention
reads: the keys AFTER the convolutions, the norm and the rotation, and
the values.

*MoE*: ``r_l = W_d u + gamma_l r_(l-1)`` is handed from layer to layer
inside one program (an activation, not slot state); an MLP of two hidden
layers scores the experts, the pick is the arg max of the softmax plus a
frozen balancing bias and its weight the pick's own probability
(ops/cca_ops.py ``mlp_router``); the experts are layers/nn.py
``moe_experts``' op, every expert held. A row that is no token — an
empty slot, a prompt's padding — is routed nowhere.

``build_cca_moe_lm`` returns what models/transformer.py build_decoder_lm
returns (models/served_lm.py ``program_set``), and a slot owns TWO kinds
of persistable state: ``kv_cache.*`` — narrower than the model's heads:
c d_h key and c d_h value columns a position a layer — and
``conv_state.*``, three one-row windows a layer: the latent ``z`` at t-1
(``.z``), the first convolution's output at t-1 (``.a``) and ``W_v2 u``
at t-1 (``.v``). A prefill into a slot overwrites every one of them with
what the prompt's last REAL token left.

A prefill or decode program's fetch is its tokens and then what the step
observed (``observed_layout``): a decode step's rows by expert summed
over the layers and the experts any row reached, and every layer's picks
— the server's counters and a benchmark's comparison read them with the
tokens, in the one fetch a step already makes.

Storage is by kind (``dtypes``): weights, KV and windows at ``weights`` /
``kv`` / ``conv`` (bfloat16 as served); norm scales, tau, gamma, the
balancing bias and the router's arrays float32. There is no float32
master copy of anything.
"""
from __future__ import annotations

import math

from .. import layers
from ..initializer import (ConstantInitializer, NormalInitializer,
                           UniformInitializer)
from ..layer_helper import LayerHelper, ParamAttr
from .decoder_moe import _embed, _heads, _linear, _rope_keywords
from .served_lm import (create_states, last_real_rows, mode_feeds,
                        program_set, rms, tied_head)
from .transformer import (KV_CACHE_PREFIX, LMProgram, _cache_update,
                          _sdpa_op)

CONV_STATE_PREFIX = "conv_state."
STATE_PREFIXES = (KV_CACHE_PREFIX, CONV_STATE_PREFIX)
#: the three windows a layer, and what each holds
WINDOWS = ("z", "a", "v")

#: what is stored at what width, by kind
SERVED_DTYPES = {"weights": "bfloat16", "kv": "bfloat16",
                 "conv": "bfloat16", "scales": "float32"}

# the published keys the builder reads (every one is required)
ARCH_KEYS = (
    "hidden_size", "head_dim", "num_attention_heads",
    "num_key_value_heads", "cca_time0", "cca_time1", "layer_types",
    "rope_parameters", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "router_hidden_size", "rms_norm_eps")

# initial values the published config has no key for (the configuration
# file's ``assumed`` says why each)
GAMMA_INIT = 0.5


def state_names(n_layer) -> dict:
    """{kind: [persistable state names]}: K and V and three windows a
    layer."""
    return {"kv": [f"{KV_CACHE_PREFIX}l{i}.{w}" for i in range(n_layer)
                   for w in "kv"],
            "conv": [f"{CONV_STATE_PREFIX}l{i}.{w}" for i in range(n_layer)
                     for w in WINDOWS]}


def observed_layout(arch, mode, seq_len, slots) -> dict:
    """{what: (offset, shape)} of what a program's fetch carries after
    its tokens (prefill: 1 token; decode: ``slots``): ``expert_rows``
    [experts + 1] (decode alone) and ``picks`` [layers, rows]."""
    n_layer, experts = len(arch["layer_types"]), arch["num_experts"]
    if mode == "decode":
        return {"expert_rows": (0, (experts + 1,)),
                "picks": (experts + 1, (n_layer, slots))}
    return {"picks": (0, (n_layer, seq_len))} if mode == "prefill" else {}


def _check(arch):
    missing = [k for k in ARCH_KEYS if arch.get(k) is None]
    if missing:
        raise ValueError(f"cca_moe: the architecture lacks {missing}")
    odd = sorted(set(arch["layer_types"]) - {"hybrid"})
    if odd:
        raise ValueError(f"cca_moe: layer_types holds {odd}; every layer "
                         "is 'hybrid' (an attention and an expert sublayer)")
    if int(arch["num_experts_per_tok"]) != 1:
        raise ValueError("cca_moe routes top-1 (num_experts_per_tok 1)")
    if arch["num_attention_heads"] % arch["num_key_value_heads"] or \
            arch["num_key_value_heads"] % 2:
        raise ValueError("cca_moe: the query heads divide by the key "
                         "heads, and the value heads split in two halves")


def _sizes(arch):
    width = arch["head_dim"]
    n_head, n_kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    return dict(width=width, q=n_head * width, kv=n_kv * width,
                latent=(n_head + n_kv) * width, shifted=n_kv // 2 * width)


def _state_shapes(arch, slots, max_seq_len, dtypes):
    size = _sizes(arch)
    cache = ([slots, arch["num_key_value_heads"], max_seq_len,
              size["width"]], dtypes["kv"])
    window = {"z": (arch["cca_time0"] - 1) * size["latent"],
              "a": (arch["cca_time1"] - 1) * size["latent"],
              "v": size["shifted"]}
    names = state_names(len(arch["layer_types"]))
    shapes = {name: cache for name in names["kv"]}
    shapes.update({name: ([slots, window[name.rsplit(".", 1)[1]]],
                          dtypes["conv"]) for name in names["conv"]})
    return shapes


def _parameter(helper, shape, dtype, init, is_bias=False, **attr):
    return helper.create_parameter(ParamAttr(initializer=init, **attr),
                                   shape, dtype, is_bias=is_bias)


def _cca_parameters(arch, dtype):
    """The mixer's small parameters, made by every program alike (the
    projections come from ``_linear`` where they are used): each
    convolution's taps and bias at the weights' width, drawn U(-b, b)
    with b = 1 / sqrt(fan-in) (a tap reads 1 input column depthwise,
    ``head_dim`` grouped); tau = 1 a key head, float32."""
    size = _sizes(arch)
    t0, t1, width = arch["cca_time0"], arch["cca_time1"], size["width"]
    helper = LayerHelper("cca")

    def drawn(shape, fan_in, **kw):
        bound = 1.0 / math.sqrt(fan_in)
        return _parameter(helper, shape, dtype,
                          UniformInitializer(-bound, bound), **kw)

    return dict(
        w0=drawn([t0, size["latent"]], t0),
        b0=drawn([size["latent"]], t0, is_bias=True),
        w1=drawn([t1 * size["latent"], width], t1 * width),
        b1=drawn([size["latent"]], t1 * width, is_bias=True),
        tau=_parameter(helper, [arch["num_key_value_heads"]], "float32",
                       ConstantInitializer(1.0)))


def _router_parameters(arch, first):
    """mlp_router's inputs by slot, float32: Xavier matrices, zero
    biases, gamma (no carried term in the first layer: no Gamma) and
    the frozen balancing bias at 0."""
    d, h = arch["hidden_size"], arch["router_hidden_size"]
    experts = arch["num_experts"]
    helper = LayerHelper("router")

    def matrix(rows, cols):
        limit = (6.0 / (rows + cols)) ** 0.5
        return _parameter(helper, [rows, cols], "float32",
                          UniformInitializer(-limit, limit))

    def vector(n, value, **kw):
        return _parameter(helper, [n], "float32",
                          ConstantInitializer(value), **kw)

    out = dict(WDown=matrix(d, h), W1=matrix(h, h),
               B1=vector(h, 0.0, is_bias=True), W2=matrix(h, h),
               B2=vector(h, 0.0, is_bias=True), W3=matrix(h, experts))
    if not first:
        out["Gamma"] = vector(1, GAMMA_INIT)
    out["SelectBias"] = vector(experts, 0.0, is_bias=True, trainable=False)
    return out


def _shift_taps(width, dtype):
    """(W [2, width], Bias [width]) under which ops/ssm_ops.py's
    depthwise convolution IS a shift by one token: tap 0 (the input one
    row back) 1, tap 1 (this row's) 0 — constants of the program, no
    parameters."""
    return (layers.concat([layers.fill_constant([1, width], dtype, 1.0),
                           layers.fill_constant([1, width], dtype, 0.0)],
                          axis=0),
            layers.fill_constant([width], dtype, 0.0))


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
    n_layer, width = len(arch["layer_types"]), size["width"]
    rope = _rope_keywords(arch["rope_parameters"]["hybrid"], width)
    out_scale = (2.0 * n_layer) ** -0.5
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed
    with pt.program_guard(main, startup), framework.isolated_name_scope():
        decode = mode == "decode"
        ids, positions, lengths, slot, feeds = mode_feeds(mode, seq_len,
                                                          slots)
        states = create_states(_state_shapes(
            arch, slots, max_seq_len, dtypes)) if mode != "full" else {}
        if not decode:
            positions = layers.range(0, seq_len, 1, "int64")
        shift_w, shift_b = _shift_taps(size["shifted"], w_dtype)

        helper = LayerHelper("cca_moe_lm")
        table = helper.create_parameter(
            ParamAttr(initializer=NormalInitializer(0.0, embedding_std)),
            [vocab_size, d], w_dtype)
        x = _embed(table, ids)

        def window(i, which):
            return states[f"{CONV_STATE_PREFIX}l{i}.{which}"]

        def convolved(i, which, t, op, step_op, *weights):
            """t through one causal convolution: a prompt with its
            lengths (a prefill writes the window the last real token
            left into the slot), or one token a slot against the
            window."""
            if decode:
                return step_op(window(i, which), t, *weights)
            out, last = op(t, *weights[:2], lengths, *weights[2:])
            if mode == "prefill":
                layers.slot_state_write(window(i, which), last, slot)
            return out

        def turned(t, heads):
            """[n, S, heads * width] -> [n, heads, S, width], the
            rotary part of every head turned at each row's position: a
            prompt's rows 0 .. S - 1, a decode step's slots each at its
            OWN position (the slots moved onto the op's row axis)."""
            if not decode:
                return layers.rotary_embedding(_heads(t, heads, width),
                                               positions, **rope)
            rows = layers.transpose(
                layers.reshape(t, [0, heads, width]), [1, 0, 2])
            rows = layers.rotary_embedding(rows, positions, **rope)
            return layers.unsqueeze(layers.transpose(rows, [1, 0, 2]), [2])

        def cca(i, u):
            p = _cca_parameters(arch, w_dtype)
            z = layers.concat([_linear(u, size["q"], "cca_q"),
                               _linear(u, size["kv"], "cca_k")], axis=2)
            a = convolved(i, "z", z, layers.causal_conv1d,
                          layers.conv_state_update, p["w0"], p["b0"])
            b = convolved(
                i, "a", a, layers.grouped_causal_conv1d,
                layers.grouped_conv_state_update, p["w1"], p["b1"],
                n_head + n_kv)
            q, k = layers.cca_qk_mix(z, b, p["tau"], n_head, n_kv)
            v = layers.concat([
                _linear(u, size["shifted"], "cca_v1"),
                convolved(i, "v", _linear(u, size["shifted"], "cca_v2"),
                          layers.causal_conv1d, layers.conv_state_update,
                          shift_w, shift_b)], axis=2)
            q, k, v = turned(q, n_head), turned(k, n_kv), \
                _heads(v, n_kv, width)
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
                                    [0, 0, size["q"]])
            return _linear(merged, d, "cca_o", out_scale)

        r, counts, picks = None, [], []
        for i in range(n_layer):
            x = layers.elementwise_add(x, cca(i, rms(x, eps)))
            u = rms(x, eps)
            idx, weights, r, sent = layers.mlp_router(
                u, lengths, _router_parameters(arch, first=i == 0), r)
            x = layers.elementwise_add(x, layers.moe_experts(
                u, idx, weights, arch["moe_intermediate_size"],
                arch["num_experts"], down_init_scale=out_scale,
                tally=False))
            counts.append(sent)
            picks.append(layers.reshape(idx, [-1]))

        if not decode:
            x = last_real_rows(x, lengths, seq_len, w_dtype)   # [n, 1, d]
        logits = tied_head(x, table, eps)                  # [n, 1, V]
        fetch = layers.argmax(logits, axis=-1)             # [n, 1]
        if mode != "full":
            # the tokens, then what the step observed (observed_layout)
            seen = ([layers.sums(counts)] if decode else []) + picks
            fetch = layers.concat(
                [layers.reshape(fetch, [-1])]
                + [layers.cast(t, "int64") for t in seen], axis=0)
    return LMProgram(main, startup, feeds, fetch.name)


def build_cca_moe_lm(arch, vocab_size=1000, max_seq_len=64, slots=4,
                     prompt_buckets=(16, 32, 64), cache_buckets=(32, 64),
                     seed=0, dtypes=None, embedding_std=0.02):
    """The generation program set of one CCA / expert stack
    (models/served_lm.py ``program_set``; "observed": ``observed_layout``
    bound to this stack, for serving/generation/model.py). ``arch``
    holds the published config keys (ARCH_KEYS), ``dtypes`` the storage
    width by kind (SERVED_DTYPES where None)."""
    _check(arch)
    dtypes = dict(SERVED_DTYPES, **(dtypes or {}))
    args = (arch, vocab_size, max_seq_len, slots, seed, dtypes,
            float(embedding_std))
    out = program_set(
        lambda mode, bucket: _build_program(mode, bucket, *args),
        max_seq_len, prompt_buckets, cache_buckets,
        state_names(len(arch["layer_types"])), STATE_PREFIXES)
    out["observed"] = lambda mode, bucket: observed_layout(
        arch, mode, bucket, slots)
    return out
