"""models/decoder_moe.py at toy widths against chipbench/reference_joyai.py
(an independent f32 ``jax.numpy`` forward with no sort, no grouped
product and no kernel): both losses, every parameter gradient — the
embedding table's and the head's are sums of the main model's use and the
prediction module's — and one Adam step, through both attention paths."""
import collections

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from chipbench import reference, reference_joyai
from paddle_tpu.core.registry import grad_var_name
from paddle_tpu.models import decoder_moe
from paddle_tpu.observability import default_registry

S = 16
# 2 + 1 layers, 8 experts of which this chip holds 2 (experts 2 and 3),
# top-2: the configuration's rehearsal sizes, offset apart
MODEL = dict(trg_vocab=96, hidden_size=32, num_attention_heads=2,
             q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, intermediate_size=64,
             moe_intermediate_size=24, n_routed_experts=8, experts_held=2,
             expert_offset=2, num_experts_per_tok=2, n_shared_experts=1,
             scoring_func="sigmoid", norm_topk_prob=True,
             routed_scaling_factor=2.5, rope_theta=32000000.0,
             rope_interleave=True, rms_norm_eps=1e-6,
             num_hidden_layers=2, first_k_dense_replace=1,
             num_nextn_predict_layers=1, mtp_loss_weight=0.3, lr=1e-3)
KNOB = {"flash": "force", "composed": "0"}


def _counts(name):
    fam = default_registry().get(name)
    if fam is None:
        return collections.Counter()
    return collections.Counter(
        {labels: child.value for labels, child in fam.samples()})


def _batch(seed, rows=3):
    rng = np.random.default_rng(seed)
    feed = {k: rng.integers(1, MODEL["trg_vocab"], (rows, S, 1),
                            dtype=np.int64)
            for k in ("src_ids", "trg_ids", "trg_labels")}
    feed["pos_ids"] = np.arange(S, dtype=np.int64)
    return feed


def _started(**kw):
    pt.reset_default_programs()
    pt.reset_global_scope()
    main, startup, fetch = decoder_moe.build_train(
        max_len=S, **dict(MODEL, **kw))
    exe = pt.Executor()
    exe.run(startup)
    names = [p.name for p in main.all_parameters()]
    tape = [np.array(pt.global_scope().get(n)) for n in names]
    return main, fetch, exe, names, tape


def _close(got, want, rtol, atol_rel, what):
    want = np.asarray(want)
    np.testing.assert_allclose(
        np.asarray(got), want, rtol=rtol,
        atol=atol_rel * max(float(np.abs(want).max()), 1e-3), err_msg=what)


@pytest.mark.parametrize("path", ["composed", "flash"])
def test_loss_and_every_gradient_match_the_reference_in_f32(
        monkeypatch, path):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", KNOB[path])
    main, fetch, exe, names, tape = _started()
    trainable = [p.name for p in main.all_parameters() if p.trainable]
    batch = _batch(0)
    sdpa, moe = (_counts("paddle_tpu_sdpa_sites_total"),
                 _counts("paddle_tpu_moe_sites_total"))
    loss, *grads = exe.run(
        main, feed=batch,
        fetch_list=[fetch["loss"]] + [grad_var_name(n) for n in trainable])
    # 2 blocks + the prediction module's: causal, maskless attention
    # sites and (the dense block apart) expert-layer sites
    assert dict(_counts("paddle_tpu_sdpa_sites_total") - sdpa) == \
        {(path, "none", "1", "0", "1", "bhsd"): 3}
    assert dict(_counts("paddle_tpu_moe_sites_total") - moe) == \
        {("ragged_dot", "2", "8"): 2}
    want = reference_joyai.loss(tape, batch, MODEL)
    np.testing.assert_allclose(float(np.asarray(loss).reshape(())), want,
                               rtol=1e-5)
    want_grads = dict(zip(names, reference_joyai.grads(tape, batch, MODEL)))
    for name, got in zip(trainable, grads):
        _close(got, want_grads[name], 2e-3, 1e-5, name)
    frozen = set(names) - set(trainable)
    assert len(frozen) == 2          # one selection bias an expert layer
    for name in frozen:
        assert not np.asarray(want_grads[name]).any(), name


def test_one_adam_step_is_the_references_first_update():
    main, fetch, exe, names, tape = _started()
    batch = _batch(1)
    exe.run(main, feed=batch, fetch_list=[fetch["loss"]])
    after = [np.array(pt.global_scope().get(n)) for n in names]
    grads = reference_joyai.grads(tape, batch, MODEL)
    wanted = reference.adam_first_step(grads, MODEL["lr"])
    applied = [b - a for a, b in zip(tape, after)]
    share = reference.descent_share(grads, applied, wanted)
    assert share["overall"] == pytest.approx(1.0, abs=2e-3)
    scored = [s for s in share["per_array"] if s is not None]
    # an array nobody updates (the selection bias) goes unscored
    assert len(scored) == len(names) - 2
    assert min(scored) > 0.98 and max(scored) < 1.02
    for name, a, b in zip(names, tape, after):
        if "moe_router" in name and a.ndim == 1:
            np.testing.assert_array_equal(a, b, err_msg=name)


def test_amp_step_stays_within_bf16_of_the_reference():
    """bf16 matmul operands and activations against the f32 reference:
    8 mantissa bits give each product a relative error near 4e-3, and
    at toy widths (logits of order 1, 144 tokens) nothing averages it
    away — the chip's cell, with 4,096 tokens and logits much smaller
    than 1, is held to 5e-5 by the driver. 2e-2 is five times the
    rounding and far under a dropped layer or a wrong routing weight
    (1e-1 and more here)."""
    main, fetch, exe, names, tape = _started()
    batch = _batch(2)
    with pt.amp.amp_guard():
        loss, = exe.run(main, feed=batch, fetch_list=[fetch["loss"]])
    after = [np.array(pt.global_scope().get(n)) for n in names]
    want = reference_joyai.loss(tape, batch, MODEL)
    np.testing.assert_allclose(float(np.asarray(loss).reshape(())), want,
                               rtol=2e-2)
    grads = reference_joyai.grads(tape, batch, MODEL)
    share = reference.descent_share(
        grads, [b - a for a, b in zip(tape, after)],
        reference.adam_first_step(grads, MODEL["lr"]))
    assert share["overall"] > 0.9


def _live_rows():
    scope = pt.global_scope()
    return {n: np.asarray(scope.get(n)) for n in scope.local_names()
            if n.endswith(".live_rows")}


def test_holding_every_expert_is_the_default():
    main, fetch, exe, names, tape = _started(experts_held=None,
                                             expert_offset=0)
    batch = _batch(3, rows=2)
    loss, = exe.run(main, feed=batch, fetch_list=[fetch["loss"]])
    want = reference_joyai.loss(tape, batch,
                                dict(MODEL, experts_held=None,
                                     expert_offset=0))
    np.testing.assert_allclose(float(np.asarray(loss).reshape(())), want,
                               rtol=1e-5)
    # every pick is held: tokens x top_k live rows a layer, every step,
    # in a buffer of as many (one pass over the worst case)
    exe.run(main, feed=_batch(4, rows=2), fetch_list=[fetch["loss"]])
    rows = 2 * S * MODEL["num_experts_per_tok"]
    tallies = _live_rows()
    assert len(tallies) == 2 and not set(tallies) & set(names)
    for name, tally in tallies.items():
        np.testing.assert_array_equal(tally, [2 * rows, 2, rows, 2 * rows],
                                      name)


def test_each_expert_layer_tallies_the_rows_its_routing_sent():
    """Experts 2 and 3 of 8 under top-2: the first expert layer's
    count is the reference's routing of the same hidden state, counted
    by hand; the tally is no parameter and the update leaves it to the
    program."""
    main, fetch, exe, names, tape = _started()
    batch = _batch(5)
    exe.run(main, feed=batch, fetch_list=[fetch["loss"]])
    first = sorted(_live_rows().items())[0][1]
    m = dict(MODEL)
    ids = np.asarray(batch["trg_ids"]).reshape(3, S)
    pos = np.arange(S, dtype=np.int32)
    w = [jnp.asarray(a) for a in tape]
    x = reference_joyai.block(w[0][ids], pos, w[1:13], m, True)
    blk = w[13:30]
    x = x + reference_joyai.latent_attention(
        reference_joyai.rms_norm(x, blk[0], 1e-6), pos, blk[1:8], m)
    idx, _ = reference_joyai.route(
        reference_joyai.rms_norm(x, blk[8], 1e-6), blk[9], blk[10], m)
    want = int(np.sum((np.asarray(idx) >= 2) & (np.asarray(idx) < 4)))
    assert 0 < want < 3 * S * 2
    # under 512 rows a block is the whole worst case, tokens x 2
    np.testing.assert_array_equal(first, [want, 1, want, 3 * S * 2])


def test_another_loss_weight_and_depth_still_match_the_reference():
    """``mtp_loss_weight`` reaches the loss and ``init_depth`` only the
    draw of the projections into the residual stream."""
    kw = dict(mtp_loss_weight=0.7, init_depth=18)
    main, fetch, exe, names, tape = _started(**kw)
    batch = _batch(6, rows=2)
    loss, = exe.run(main, feed=batch, fetch_list=[fetch["loss"]])
    m = dict(MODEL, **kw)
    want = reference_joyai.loss(tape, batch, m)
    np.testing.assert_allclose(float(np.asarray(loss).reshape(())), want,
                               rtol=1e-5)
    assert abs(want - reference_joyai.loss(tape, batch, MODEL)) > 0.1
    _, _, _, plain_names, plain = _started()
    assert plain_names == names
    scaled = 0
    for name, a, b in zip(names, tape, plain):
        if a.ndim != 2 or a.shape[0] == MODEL["trg_vocab"]:
            continue
        ratio = np.abs(a).max() / np.abs(b).max()
        into_stream = a.shape[-1] == MODEL["hidden_size"] and (
            "mla_o" in name or "_down" in name or "moe_experts" in name)
        if into_stream:
            scaled += 1
        # depth 2 by default against 18: a third of the range
        assert ratio == pytest.approx(1 / 3 if into_stream else 1.0,
                                      rel=0.05), name
    assert scaled == 3 + 1 + 2 + 2   # attention, dense, shared, experts


@pytest.mark.parametrize("key,value", [("scoring_func", "softmax"),
                                       ("norm_topk_prob", False),
                                       ("rope_interleave", False)])
def test_builder_refuses_what_it_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        decoder_moe.build_train(max_len=S, **dict(MODEL, **{key: value}))
