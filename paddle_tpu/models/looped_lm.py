"""Looped decoder-only language model for the trainer: a stack of
``num_hidden_layers`` blocks run ``total_ut_steps`` times on the SAME
weights, an exit gate after every pass and a loss over all the exits
("Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741).

    x_0 = E[tokens]
    for t = 1..T:                       one StaticRNN(steps=T): ONE loop op
        h = x_(t-1)                     over ONE sub-block, one lax.scan
        for l = 1..L:
            h = h + RMS(Attn_l(RMS(h)))     a norm before AND after each
            h = h + RMS(FFN_l (RMS(h)))     sub-layer (sandwich)
        x_t = RMS(h)                    the final norm is INSIDE the loop
    z_t = x_t W_head;  lam_t = sigmoid(x_t w_g + b_g)
    p_1 = lam_1, p_t = lam_t prod_(j<t)(1 - lam_j), p_T = prod_(j<T)(1 - lam_j)
    loss = mean over tokens of sum_t p_t (CE(z_t, label) + beta log p_t)

which is the expected cross entropy under the exit distribution p less
``beta`` times p's entropy. The blocks are ``decoder_moe``'s attention
and gated FFN, called with one head count and one rotary block for all
layers; the sub-block's ops do not depend on T, and outside the loop
only the exit distribution's few ops a pass do. The L blocks' parameters
and the final norm's are made once, inside the loop's guard, and read by
the body as closure: the loop's grad op returns ONE gradient array a
parameter, the sum of its T uses made inside the scan's transpose.

The gate's product, the exit distribution and the loss terms are
float32 whatever the precision of the step, as a router's scores are:
the gate is a multiply and a sum over d (``layers.fc``'s matmul rounds
its product to bfloat16 under AMP). The gate keeps a persistable
``<gate>.exit_mass`` [T + 1] that no optimizer touches: the mean p_t of
each pass summed over the steps run, then the steps.

Initialisation: the embedding N(0, 1), every matrix plain Xavier (each
sub-layer's output passes a norm before it joins the stream, so the
scale a projection writes at is immaterial: no ``init_depth``), norm
scales 1, the gate's bias 0.

``build_train`` feeds the trainer's four names: ``trg_ids`` the tokens
t_i, ``trg_labels`` t_(i+1), ``pos_ids`` the positions; ``src_ids`` is
fed and unused.
"""
from __future__ import annotations

from .. import layers, optimizer as opt
from ..initializer import (ConstantInitializer, NormalInitializer,
                           UniformInitializer)
from ..layer_helper import LayerHelper, ParamAttr
from ..layers.control_flow import StaticRNN
from ..observability.registry import add_global_collector
from .decoder_moe import _embed, gated_ffn, gqa_attention

# log(max(p, this)): an exit the gates have closed (p = 0 in float32)
# adds nothing to the entropy instead of 0 x -inf
LOG_FLOOR = 1e-20


def sandwich_block(h, positions, cfg, layer):
    """h [b, S, d] -> the same: both sub-layers between two norms."""
    eps = cfg["rms_norm_eps"]
    a = gqa_attention(layers.rms_norm(h, eps), positions, cfg, layer)
    h = layers.elementwise_add(h, layers.rms_norm(a, eps))
    f = gated_ffn(layers.rms_norm(h, eps), cfg["intermediate_size"], cfg,
                  "ffn")
    return layers.elementwise_add(h, layers.rms_norm(f, eps))


def looped_stack(x, positions, cfg):
    """x [b, S, d] -> [T, b, S, d], every pass's normed output."""
    loop = StaticRNN(steps=cfg["total_ut_steps"])
    with loop.step():
        carried = h = loop.memory(init=x)
        for i in range(cfg["num_hidden_layers"]):
            h = sandwich_block(h, positions, cfg, i)
        out = layers.rms_norm(h, cfg["rms_norm_eps"])
        loop.update_memory(carried, out)
        loop.step_output(out)
    return loop()


def exit_gate(stacked):
    """sigmoid(x w + b) in float32: [T, b, S, d] -> [T, b, S, 1]."""
    helper = LayerHelper("exit_gate")
    d = int(stacked.shape[-1])
    limit = (6.0 / (d + 1)) ** 0.5
    w = helper.create_parameter(
        ParamAttr(initializer=UniformInitializer(-limit, limit)), [d, 1],
        "float32")
    b = helper.create_parameter(
        ParamAttr(initializer=ConstantInitializer(0.0)), [1], "float32")
    product = layers.reduce_sum(
        layers.elementwise_mul(layers.cast(stacked, "float32"),
                               layers.reshape(w, [d])),
        dim=-1, keep_dim=True)
    return layers.sigmoid(layers.elementwise_add(product, b)), helper.name


def exit_distribution(lam, passes):
    """lam [T, b, S, 1] -> [p_1 .. p_T], each [1, b, S, 1]: a token
    leaves after pass t with lam_t of what is left, after the last pass
    with all of it."""
    left, out = None, []
    for t in range(passes - 1):
        lam_t = layers.slice(lam, [0], [t], [t + 1])
        out.append(lam_t if left is None
                   else layers.elementwise_mul(lam_t, left))
        stay = layers.scale(lam_t, scale=-1.0, bias=1.0)
        left = stay if left is None else layers.elementwise_mul(left, stay)
    out.append(left if left is not None else layers.scale(
        layers.slice(lam, [0], [0], [1]), scale=0.0, bias=1.0))
    return out


def _tally_exit_mass(gate_name, exits):
    """<gate>.exit_mass += [mean p_1 .. mean p_T, 1]."""
    helper = LayerHelper("exit_mass")
    tally = helper.create_global_variable(
        shape=[len(exits) + 1], dtype="float32", persistable=True,
        name=gate_name + ".exit_mass")
    helper.set_variable_initializer(tally, ConstantInitializer(0.0))
    step = layers.concat(
        [layers.reshape(layers.mean(p), [1]) for p in exits]
        + [layers.fill_constant([1], "float32", 1.0)], axis=0)
    step.stop_gradient = True
    helper.append_op(type="elementwise_add",
                     inputs={"X": tally, "Y": step},
                     outputs={"Out": tally}, attrs={"axis": -1})


def exit_weighted_loss(ces, exits, beta):
    """mean over tokens of sum_t p_t (ce_t + beta log p_t): the
    expected cross entropy under the exit distribution less beta times
    its entropy. ``ces`` and ``exits`` are T arrays [1, b, S, 1]."""
    terms = []
    for ce, p in zip(ces, exits):
        log_p = layers.log(layers.clip(p, LOG_FLOOR, 1.0))
        terms.append(layers.elementwise_mul(p, layers.elementwise_add(
            ce, layers.scale(log_p, scale=beta))))
    return layers.mean(layers.sums(terms) if len(terms) > 1 else terms[0])


def exit_losses(stacked, labels, cfg):
    """The loss over the stacked pass outputs [T, b, S, d]: ONE head
    product, a cross entropy a pass against the same labels, the gate
    and the exit distribution."""
    d, passes = cfg["hidden_size"], cfg["total_ut_steps"]
    helper = LayerHelper("looped_lm_head")
    head = helper.create_parameter(None, [d, cfg["trg_vocab"]], "float32")
    logits = layers.mul(stacked, head, x_num_col_dims=3)
    lam, gate_name = exit_gate(stacked)
    exits = exit_distribution(lam, passes)
    labels = layers.unsqueeze(labels, [0])
    ces = [layers.softmax_with_cross_entropy(
        layers.slice(logits, [0], [t], [t + 1]), labels)
        for t in range(passes)]
    _tally_exit_mass(gate_name, exits)
    return exit_weighted_loss(ces, exits, cfg["exit_entropy_beta"])


def _export_exit_mass(registry):
    """/metrics: every ``<gate>.exit_mass`` tally of the global scope as
    the share of a token's probability that left after each pass, over
    the steps run so far (observability/registry.py runs it at scrape
    time; a tally that has counted no step yet is left out)."""
    import numpy as np
    import paddle_tpu as pt
    scope = pt.global_scope()
    for name in scope.local_names():
        if not name.endswith(".exit_mass"):
            continue
        tally = np.asarray(scope.get(name))
        if tally[-1] <= 0:
            continue
        family = registry.gauge(
            "paddle_tpu_exit_mass_share",
            "Mean exit probability a pass of a looped stack's exit gate "
            "(p_t of models/looped_lm.py: what leaves after pass t), "
            "over the training steps run so far, from the program's own "
            "<gate>.exit_mass tally.", ("gate", "exit"))
        for t, mass in enumerate(tally[:-1]):
            family.labels(gate=name[:-len(".exit_mass")],
                          exit=str(t + 1)).set(float(mass / tally[-1]))


add_global_collector(_export_exit_mass)


def looped_lm(tokens, labels, positions, cfg):
    """The training loss of the equations above."""
    helper = LayerHelper("looped_lm")
    table = helper.create_parameter(
        ParamAttr(initializer=NormalInitializer(0.0, 1.0)),
        [cfg["trg_vocab"], cfg["hidden_size"]], "float32")
    stacked = looped_stack(_embed(table, tokens), positions, cfg)
    return exit_losses(stacked, labels, cfg)


def model_cfg(cfg):
    """``cfg`` with what decoder_moe's attention and FFN read by layer:
    one head count and one rotary block for all layers, rotate-half over
    the whole head, no window, no gate, no scaled initialisation."""
    n = cfg["num_hidden_layers"]
    return dict(
        cfg, num_attention_heads_per_layer=[cfg["num_attention_heads"]] * n,
        layer_types=["full_attention"] * n,
        rope_parameters={"full_attention": {
            "rope_theta": cfg["rope_theta"]}},
        sliding_window=None, gating=False, init_depth=None)


def build_train(trg_vocab=1024, max_len=64, lr=1e-3, hidden_size=64,
                intermediate_size=128, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                rope_theta=10000.0, rms_norm_eps=1e-6, total_ut_steps=4,
                exit_entropy_beta=0.1):
    """(main, startup, {"loss": var}); the keywords are the published
    config's keys, ``exit_entropy_beta`` the weight of the exit
    distribution's entropy in the loss."""
    import paddle_tpu as pt
    if num_attention_heads % num_key_value_heads:
        raise ValueError(
            f"num_attention_heads={num_attention_heads} is no multiple "
            f"of num_key_value_heads={num_key_value_heads}")
    cfg = model_cfg(dict(locals()))
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        layers.data("src_ids", [max_len, 1], dtype="int64")
        tokens = layers.data("trg_ids", [max_len, 1], dtype="int64")
        labels = layers.data("trg_labels", [max_len, 1], dtype="int64")
        pos = layers.data("pos_ids", [max_len], dtype="int64",
                          append_batch_size=False)
        loss = looped_lm(tokens, labels, pos, cfg)
        opt.AdamOptimizer(learning_rate=lr).minimize(loss)
    return main, startup, {"loss": loss}
