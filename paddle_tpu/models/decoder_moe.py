"""Decoder-only pre-norm language model with latent or grouped-query
attention and sparse experts, for the trainer (ROADMAP B2(c), B4, B5, B6;
the block of DeepSeek-V2/V3, arXiv:2405.04434 and arXiv:2412.19437, and
the window / full attention stacks that share its expert layer).

Every block is ``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``:

* ``attention="gqa"``: grouped-query attention, layer by layer
  (``layer_types``, ``num_attention_heads_per_layer``): K and V at
  ``num_key_value_heads`` heads, rotary embedding in the rotate-half
  layout at the layer kind's ``rope_parameters`` block (a partial width,
  a YaRN table), a ``sliding_window`` on the "sliding_attention" layers —
  the fused attention op is handed K and V at their own head count and
  the window as an attr — and, under ``gating``, each head's output
  times a sigmoid gate of the layer's input before the output
  projection (arXiv:2505.06708, head-wise);
* ``attention="mla"``: multi-head latent attention in its plain form: queries
  and keys/values come through low-rank latents with an RMS norm on each,
  a query/key head is a position-free part beside a rotary part (ONE
  rotary key shared by all heads), and a value head may be narrower than
  a key head — K is materialised at full width and the fused attention op
  does the rest, causal by its attr;
* the first ``first_k_dense_replace`` blocks (or those ``mlp_layer_types``
  calls "dense") have a dense gated FFN, the
  others a router over ``n_routed_experts``, the expert layer's HELD
  share (``experts_held`` from ``expert_offset``: what this chip of an
  expert-parallel group owns; layers/nn.py moe_experts) and shared
  experts computed in full;
* ``num_nextn_predict_layers`` multi-token-prediction modules
  (arXiv:2412.19437 section 2.2) follow the stack: module j joins the
  previous depth's normed hidden state at position i with the embedding
  of token i + j, runs one more block and predicts token i + j + 1
  through the SAME embedding table and output head as the main model —
  the backward pass sums each shared parameter's uses.

Initialisation: the embedding is N(0, 1) and every projection that writes
into the residual stream (the attention output, every down projection)
is Xavier times 1 / sqrt(2 x ``init_depth``) — the scaled initialisation
of GPT-2 and Megatron — so a fresh block adds a few per cent to the
stream, as a trained one does. Under plain Xavier the first FFN's output
is thirty times the embedding and ONE routed expert a tenth of the
stream: rounding then decides a token's eighth expert and, through it,
the loss (PERF.md, PR 36).

``build_train`` feeds the trainer's four names: ``trg_ids`` the tokens
t_i, ``trg_labels`` t_(i+1), ``src_ids`` t_(i+2) (the first module's
labels), ``pos_ids`` the positions.
"""
from __future__ import annotations

import math

from .. import layers, optimizer as opt
from ..initializer import NormalInitializer, UniformInitializer
from ..layer_helper import LayerHelper, ParamAttr
from .transformer import _sdpa_op


def _linear(x, size, name, init_scale=1.0):
    """x W, W Xavier-uniform times ``init_scale``."""
    limit = init_scale * (6.0 / (int(x.shape[-1]) + size)) ** 0.5
    return layers.fc(x, size=size, num_flatten_dims=2, bias_attr=False,
                     param_attr=ParamAttr(
                         initializer=UniformInitializer(-limit, limit)),
                     name=name)


# The frozen selection bias of a router is drawn uniformly in this range
# (the published configs give e_score_correction_bias no initial value).
SELECTION_BIAS_RANGE = 0.1


def _out_scale(cfg):
    """What a projection into the residual stream is initialised at:
    plain Xavier where the model gives no ``init_depth``."""
    return (2.0 * cfg["init_depth"]) ** -0.5 if cfg["init_depth"] else 1.0


def _heads(x, n_head, width):
    """[b, S, n_head * width] -> [b, n_head, S, width]."""
    return layers.transpose(layers.reshape(x, [0, 0, n_head, width]),
                            [0, 2, 1, 3])


def latent_attention(x, positions, cfg):
    """Multi-head latent attention over x [b, S, d], plain form."""
    n_head = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    d_v, eps = cfg["v_head_dim"], cfg["rms_norm_eps"]
    theta = cfg["rope_theta"]

    c_q = layers.rms_norm(_linear(x, cfg["q_lora_rank"], "mla_q_a"), eps)
    q = _heads(_linear(c_q, n_head * (nope + rope), "mla_q_b"),
               n_head, nope + rope)
    q_nope, q_rope = layers.split(q, [nope, rope], dim=3)
    c_kv, k_rope = layers.split(
        _linear(x, cfg["kv_lora_rank"] + rope, "mla_kv_a"),
        [cfg["kv_lora_rank"], rope], dim=2)
    kv = _heads(_linear(layers.rms_norm(c_kv, eps), n_head * (nope + d_v),
                        "mla_kv_b"), n_head, nope + d_v)
    k_nope, v = layers.split(kv, [nope, d_v], dim=3)
    # one rotary key for all heads: [b, S, rope] -> [b, 1, S, rope]
    k_rope = layers.rotary_embedding(layers.unsqueeze(k_rope, [1]),
                                     positions, theta)
    q = layers.concat([q_nope, layers.rotary_embedding(q_rope, positions,
                                                       theta)], axis=3)
    k = layers.concat([k_nope, layers.expand(k_rope, [1, n_head, 1, 1])],
                      axis=3)
    ctx = _sdpa_op(q, k, v, None, causal=True)       # [b, h, S, d_v]
    merged = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                            [0, 0, n_head * d_v])
    return _linear(merged, cfg["hidden_size"], "mla_o", _out_scale(cfg))


def yarn_inv_freq(theta, rotary_dim, factor, original_max, beta_fast,
                  beta_slow):
    """YaRN's frequency table (arXiv:2309.00071, the Hugging Face
    form): pair i keeps theta^(-2i/r) where it turns more than
    ``beta_fast`` times over the original context, takes it divided by
    ``factor`` where fewer than ``beta_slow``, a linear ramp between."""

    def pair_turning(turns):
        return rotary_dim * math.log(original_max / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    lo = max(math.floor(pair_turning(beta_fast)), 0)
    hi = min(math.ceil(pair_turning(beta_slow)), rotary_dim - 1)
    table = []
    for i in range(rotary_dim // 2):
        f = theta ** (-2.0 * i / rotary_dim)
        keep = 1.0 - min(max((i - lo) / max(hi - lo, 1e-3), 0.0), 1.0)
        table.append(f / factor * (1.0 - keep) + f * keep)
    return table


def _rope_keywords(params, head_dim):
    """layers.rotary_embedding's keywords of one ``rope_parameters``
    block: rotate-half over the first ``partial_rotary_factor`` of a
    head."""
    r = int(head_dim * float(params.get("partial_rotary_factor", 1)))
    kw = {"theta": float(params["rope_theta"]), "rotate_half": True,
          "rotary_dim": r}
    kind = params.get("rope_type", "default")
    if kind == "yarn":
        kw["inv_freq"] = yarn_inv_freq(
            kw["theta"], r, float(params["factor"]),
            int(params["original_max_position_embeddings"]),
            float(params.get("beta_fast", 32)),
            float(params.get("beta_slow", 1)))
        kw["scale"] = float(params.get("attention_factor") or
                            0.1 * math.log(float(params["factor"])) + 1.0)
    elif kind != "default":
        raise ValueError(f"rope_type {kind!r}: default or yarn")
    return kw


def gqa_attention(x, positions, cfg, layer):
    """Grouped-query attention of block ``layer`` over x [b, S, d]."""
    n_head = cfg["num_attention_heads_per_layer"][layer]
    n_kv, width = cfg["num_key_value_heads"], cfg["head_dim"]
    kind = cfg["layer_types"][layer]
    rope = _rope_keywords(cfg["rope_parameters"][kind], width)
    q = layers.rotary_embedding(
        _heads(_linear(x, n_head * width, "gqa_q"), n_head, width),
        positions, **rope)
    k = layers.rotary_embedding(
        _heads(_linear(x, n_kv * width, "gqa_k"), n_kv, width),
        positions, **rope)
    v = _heads(_linear(x, n_kv * width, "gqa_v"), n_kv, width)
    window = {"window": int(cfg["sliding_window"])} \
        if kind == "sliding_attention" else {}
    ctx = _sdpa_op(q, k, v, None, causal=True, **window)  # [b, h, S, w]
    ctx = layers.transpose(ctx, [0, 2, 1, 3])
    if cfg["gating"]:              # one gate a head, [b, S, h, 1]
        gate = layers.sigmoid(_linear(x, n_head, "gqa_gate"))
        ctx = layers.elementwise_mul(ctx, layers.unsqueeze(gate, [3]))
    merged = layers.reshape(ctx, [0, 0, n_head * width])
    return _linear(merged, cfg["hidden_size"], "gqa_o", _out_scale(cfg))


def gated_ffn(x, d_inner, cfg, name):
    gate = layers.swish(_linear(x, d_inner, name + "_gate"))
    up = _linear(x, d_inner, name + "_up")
    return _linear(layers.elementwise_mul(gate, up), cfg["hidden_size"],
                   name + "_down", _out_scale(cfg))


def moe_ffn(x, cfg):
    """Routed experts (the held share) plus the shared experts."""
    idx, weights = layers.moe_router(
        x, cfg["n_routed_experts"], cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        selection_bias=UniformInitializer(-SELECTION_BIAS_RANGE,
                                          SELECTION_BIAS_RANGE)
        if cfg["topk_method"] == "noaux_tc" else None)
    routed = layers.moe_experts(
        x, idx, weights, cfg["moe_intermediate_size"],
        cfg["n_routed_experts"], cfg["experts_held"], cfg["expert_offset"],
        down_init_scale=_out_scale(cfg))
    shared = gated_ffn(
        x, cfg["shared_expert_intermediate_size"]
        or cfg["n_shared_experts"] * cfg["moe_intermediate_size"], cfg,
        "moe_shared")
    return layers.elementwise_add(routed, shared)


def decoder_block(x, positions, cfg, dense, layer=None):
    """x [b, S, d] -> the same; ``layer`` is the block's place in the
    stack, which grouped-query attention reads its kind and head count
    by."""
    eps = cfg["rms_norm_eps"]
    h = layers.rms_norm(x, eps)
    x = layers.elementwise_add(
        x, gqa_attention(h, positions, cfg, layer)
        if cfg["attention"] == "gqa" else latent_attention(h, positions,
                                                           cfg))
    h = layers.rms_norm(x, eps)
    f = gated_ffn(h, cfg["intermediate_size"], cfg, "ffn") if dense \
        else moe_ffn(h, cfg)
    return layers.elementwise_add(x, f)


def _mean_token_loss(hidden, head, labels):
    """Mean cross entropy of hidden [b, S, d] through the shared head
    [d, vocab] against labels [b, S, 1]."""
    logits = layers.mul(hidden, head, x_num_col_dims=2)
    return layers.mean(layers.softmax_with_cross_entropy(logits, labels))


def _embed(table, ids):
    """layers.embedding over a table made once and looked up twice."""
    helper = LayerHelper("embedding")
    out = helper.create_tmp_variable(table.dtype)
    helper.append_op(type="lookup_table", inputs={"W": table, "Ids": ids},
                     outputs={"Out": out}, attrs={"padding_idx": -1})
    return out


def decoder_moe_lm(tokens, labels, positions, cfg):
    """The training loss: main-model cross entropy plus
    ``mtp_loss_weight`` times each prediction module's. ``labels[j]``
    are the tokens j + 1 ahead of ``tokens``; module j embeds
    ``labels[j - 1]`` and is scored on ``labels[j]``."""
    d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    helper = LayerHelper("decoder_moe_lm")
    table = helper.create_parameter(
        ParamAttr(initializer=NormalInitializer(0.0, 1.0)),
        [cfg["trg_vocab"], d], "float32")
    x = _embed(table, tokens)
    kinds = cfg["mlp_layer_types"]
    for i in range(cfg["num_hidden_layers"]):
        x = decoder_block(
            x, positions, cfg, layer=i,
            dense=kinds[i] == "dense" if kinds
            else i < cfg["first_k_dense_replace"])
    hidden = layers.rms_norm(x, eps)
    head = helper.create_parameter(None, [d, cfg["trg_vocab"]], "float32")
    loss = _mean_token_loss(hidden, head, labels[0])
    for j in range(1, cfg["num_nextn_predict_layers"] + 1):
        joined = layers.concat(
            [layers.rms_norm(_embed(table, labels[j - 1]), eps),
             layers.rms_norm(hidden, eps)], axis=2)
        x = decoder_block(_linear(joined, d, "mtp_join"), positions, cfg,
                          dense=False)
        hidden = layers.rms_norm(x, eps)
        loss = layers.elementwise_add(loss, layers.scale(
            _mean_token_loss(hidden, head, labels[j]),
            scale=cfg["mtp_loss_weight"]))
    return loss


def _check_gqa(cfg):
    """What grouped-query attention reads is there, for every layer."""
    if cfg["num_nextn_predict_layers"]:
        raise ValueError("the prediction module is built on latent "
                         "attention: num_nextn_predict_layers is 0 under "
                         "attention='gqa'")
    by_layer = ("layer_types", "num_attention_heads_per_layer")
    short = [k for k in by_layer + ("num_key_value_heads", "head_dim",
                                    "rope_parameters")
             if cfg[k] is None or (k in by_layer and len(cfg[k])
                                   < cfg["num_hidden_layers"])]
    if short:
        raise ValueError(
            "attention='gqa' reads num_key_value_heads, head_dim, "
            "rope_parameters and, for each of the "
            f"{cfg['num_hidden_layers']} layers, layer_types and "
            f"num_attention_heads_per_layer; missing or short: {short}")


def build_train(trg_vocab=1024, max_len=64, lr=1e-3, hidden_size=256,
                num_attention_heads=4, q_lora_rank=96, kv_lora_rank=64,
                qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                intermediate_size=512, moe_intermediate_size=64,
                n_routed_experts=16, experts_held=None, expert_offset=0,
                num_experts_per_tok=4, n_shared_experts=1,
                scoring_func="sigmoid", norm_topk_prob=True,
                routed_scaling_factor=1.0, rope_theta=10000.0,
                rope_interleave=True,
                rms_norm_eps=1e-6, num_hidden_layers=2,
                first_k_dense_replace=1, num_nextn_predict_layers=1,
                mtp_loss_weight=0.3, init_depth=None, attention="mla",
                topk_method="noaux_tc", layer_types=None,
                num_attention_heads_per_layer=None, mlp_layer_types=None,
                num_key_value_heads=None, head_dim=None,
                sliding_window=None, rope_parameters=None, gating=False,
                shared_expert_intermediate_size=None):
    """(main, startup, {"loss": var}). The keywords are the published
    config keys of the two families; of ``scoring_func``,
    ``norm_topk_prob`` and ``rope_interleave`` only the values given
    here are built, any other raises. ``attention`` chooses latent
    ("mla": the keywords up to ``rope_interleave``) or grouped-query
    attention ("gqa": ``layer_types``, ``num_attention_heads_per_layer``
    — both read by layer, their first ``num_hidden_layers`` entries —
    ``num_key_value_heads``, ``head_dim``, ``sliding_window``,
    ``rope_parameters``, ``gating``); ``topk_method`` "noaux_tc" selects
    the experts with the frozen selection bias, "greedy" by the scores
    alone; ``mlp_layer_types`` ("dense" / "sparse" by layer) stands in
    for ``first_k_dense_replace``, ``shared_expert_intermediate_size``
    for ``n_shared_experts`` x ``moe_intermediate_size``.
    ``experts_held`` / ``expert_offset`` say
    which of each layer's ``n_routed_experts`` this chip holds (all of
    them by default), ``init_depth`` the depth the projections into the
    residual stream are initialised for (``num_hidden_layers`` by
    default; the whole model's, where this program is a cut of one)."""
    import paddle_tpu as pt
    if num_nextn_predict_layers > 1:
        raise ValueError("the trainer feeds labels for one prediction "
                         "module: num_nextn_predict_layers is 0 or 1")
    if (scoring_func, norm_topk_prob, rope_interleave) != \
            ("sigmoid", True, True):
        raise ValueError(
            "decoder_moe builds sigmoid scores normalised over the picks "
            "and rotary embedding on neighbouring pairs only, not "
            f"scoring_func={scoring_func!r}, norm_topk_prob="
            f"{norm_topk_prob!r}, rope_interleave={rope_interleave!r}")
    if attention not in ("mla", "gqa") or \
            topk_method not in ("noaux_tc", "greedy"):
        raise ValueError(f"attention={attention!r} (mla or gqa), "
                         f"topk_method={topk_method!r} (noaux_tc or "
                         "greedy)")
    cfg = dict(locals())
    if attention == "gqa":
        _check_gqa(cfg)
    cfg["experts_held"] = n_routed_experts if experts_held is None \
        else experts_held
    cfg["init_depth"] = init_depth or num_hidden_layers
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        ahead2 = layers.data("src_ids", [max_len, 1], dtype="int64")
        tokens = layers.data("trg_ids", [max_len, 1], dtype="int64")
        ahead1 = layers.data("trg_labels", [max_len, 1], dtype="int64")
        pos = layers.data("pos_ids", [max_len], dtype="int64",
                          append_batch_size=False)
        loss = decoder_moe_lm(tokens, [ahead1, ahead2], pos, cfg)
        opt.AdamOptimizer(learning_rate=lr).minimize(loss)
    return main, startup, {"loss": loss}
