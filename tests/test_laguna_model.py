"""models/decoder_moe.py under ``attention="gqa"`` at the ``laguna-xs2``
configuration's rehearsal sizes against chipbench/reference_laguna.py
(an independent f32 ``jax.numpy`` forward: a masked softmax over the
whole score matrix, K and V repeated to the query heads, no sort, no
grouped product, no kernel): the loss, every parameter gradient and one
Adam step, through both attention paths; and the window itself, on one
attention layer alone."""
import collections
import json
import os

import numpy as np
import pytest

import paddle_tpu as pt
from chipbench import reference, reference_laguna
from paddle_tpu import layers
from paddle_tpu.core.registry import grad_var_name
from paddle_tpu.models import decoder_moe
from paddle_tpu.observability import default_registry

S = 24
with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "configs",
        "laguna-xs2.json")) as f:
    CONFIG = json.load(f)
# full, sliding (window 8), sliding: 6 / 8 / 8 query heads over 2 key
# heads of 16; layer 0 dense, then 8 experts of which this chip holds
# experts 2 and 3, top-2
MODEL = dict(CONFIG["builder"]["args"],
             **CONFIG["rehearse"]["builder_args"])
MODEL.update(expert_offset=2, lr=1e-3)
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
    assert all(p.trainable for p in main.all_parameters())  # no bias
    batch = _batch(0)
    sdpa, moe = (_counts("paddle_tpu_sdpa_sites_total"),
                 _counts("paddle_tpu_moe_sites_total"))
    loss, *grads = exe.run(
        main, feed=batch,
        fetch_list=[fetch["loss"]] + [grad_var_name(n) for n in names])
    # causal, maskless sites by window and by the query heads a key head
    assert dict(_counts("paddle_tpu_sdpa_sites_total") - sdpa) == \
        {(path, "none", "1", "0", "3", "bhsd"): 1,
         (path, "none", "1", "8", "4", "bhsd"): 2}
    assert dict(_counts("paddle_tpu_moe_sites_total") - moe) == \
        {("ragged_dot", "2", "8"): 2}
    want = reference_laguna.loss(tape, batch, MODEL)
    np.testing.assert_allclose(float(np.asarray(loss).reshape(())), want,
                               rtol=1e-5)
    want_grads = reference_laguna.grads(tape, batch, MODEL)
    assert len(want_grads) == len(names) == 1 + 10 + 2 * 14 + 2
    for name, got, ref in zip(names, grads, want_grads):
        _close(got, ref, 2e-3, 1e-5, name)


def test_one_adam_step_is_the_references_first_update():
    main, fetch, exe, names, tape = _started()
    batch = _batch(1)
    exe.run(main, feed=batch, fetch_list=[fetch["loss"]])
    after = [np.array(pt.global_scope().get(n)) for n in names]
    grads = reference_laguna.grads(tape, batch, MODEL)
    wanted = reference.adam_first_step(grads, MODEL["lr"])
    applied = [b - a for a, b in zip(tape, after)]
    share = reference.descent_share(grads, applied, wanted)
    assert share["overall"] == pytest.approx(1.0, abs=2e-3)
    scored = [s for s in share["per_array"] if s is not None]
    assert len(scored) == len(names)
    assert min(scored) > 0.98 and max(scored) < 1.02


def test_amp_step_stays_within_bf16_of_the_reference():
    """As tests/test_joyai_model.py holds the other configuration: 8
    mantissa bits, toy widths, 72 tokens; the chip's cell is held to
    5e-5 by the driver."""
    main, fetch, exe, names, tape = _started()
    batch = _batch(2)
    with pt.amp.amp_guard():
        loss, = exe.run(main, feed=batch, fetch_list=[fetch["loss"]])
    after = [np.array(pt.global_scope().get(n)) for n in names]
    want = reference_laguna.loss(tape, batch, MODEL)
    np.testing.assert_allclose(float(np.asarray(loss).reshape(())), want,
                               rtol=2e-2)
    grads = reference_laguna.grads(tape, batch, MODEL)
    share = reference.descent_share(
        grads, [b - a for a, b in zip(tape, after)],
        reference.adam_first_step(grads, MODEL["lr"]))
    assert share["overall"] > 0.9


def _one_attention_layer(kind):
    """gqa_attention alone over a fed x [1, S, d]: (run, x)."""
    pt.reset_default_programs()
    pt.reset_global_scope()
    cfg = dict(MODEL, layer_types=[kind], init_depth=1)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [S, cfg["hidden_size"]], dtype="float32")
        pos = layers.data("pos_ids", [S], dtype="int64",
                          append_batch_size=False)
        out = decoder_moe.gqa_attention(x, pos, cfg, 0)
    exe = pt.Executor()
    exe.run(startup)

    def run(value):
        got, = exe.run(main, feed={"x": value, "pos_ids": np.arange(
            S, dtype=np.int64)}, fetch_list=[out])
        return np.asarray(got)

    return run, np.random.default_rng(5).standard_normal(
        (1, S, cfg["hidden_size"])).astype(np.float32)


@pytest.mark.parametrize("path", ["composed", "flash"])
def test_a_token_behind_the_window_moves_a_full_layer_only(monkeypatch,
                                                           path):
    """Token 3 changes. Under the window (8 keys, the query's own among
    them) positions 3-10 see it and positions 11 on do not, bit for bit;
    a full layer's every later position moves."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", KNOB[path])
    window = MODEL["sliding_window"]
    for kind, blind_from in (("sliding_attention", 3 + window),
                             ("full_attention", S)):
        run, x = _one_attention_layer(kind)
        moved = x.copy()
        moved[0, 3] += 1.0
        a, b = run(x), run(moved)
        np.testing.assert_array_equal(a[0, :3], b[0, :3])
        changed = np.abs(a - b).max(axis=-1)[0]
        assert (changed[3:blind_from] > 1e-6).all(), (kind, changed)
        np.testing.assert_array_equal(a[0, blind_from:], b[0, blind_from:])


def test_each_layer_kind_takes_its_own_rope_block():
    """A full layer turns half of a head by the YaRN table, a sliding
    layer all of it at theta 1e4: swapping the two blocks changes the
    loss, and the reference follows the same keys."""
    main, fetch, exe, names, tape = _started()
    batch = _batch(3, rows=2)
    loss, = exe.run(main, feed=batch, fetch_list=[fetch["loss"]])
    want = reference_laguna.loss(tape, batch, MODEL)
    np.testing.assert_allclose(float(np.asarray(loss).reshape(())), want,
                               rtol=1e-5)
    blocks = MODEL["rope_parameters"]
    swapped = dict(MODEL, rope_parameters={
        "full_attention": blocks["sliding_attention"],
        "sliding_attention": blocks["full_attention"]})
    assert abs(reference_laguna.loss(tape, batch, swapped) - want) > 1e-4


@pytest.mark.parametrize("over,match", [
    (dict(attention="mqa"), "attention"),
    (dict(topk_method="group_limited_greedy"), "topk_method"),
    (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers"),
    (dict(layer_types=["full_attention"]), "layer_types"),
    (dict(num_key_value_heads=None), "num_key_value_heads"),
])
def test_builder_refuses_what_it_does_not_build(over, match):
    with pytest.raises(ValueError, match=match):
        decoder_moe.build_train(max_len=S, **dict(MODEL, **over))
