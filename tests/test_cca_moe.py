"""The compressed-convolutional-attention / expert family
(models/cca_moe.py) on the token server: prefill-then-decode LOGITS
against the plain reference (chipbench/reference_zaya.py) at every
position, through the compressed cache and the three windows; pad rows;
slots reused; the picks and the expert counters; the stored dtypes; and
the two older families' programs pinned to what they serialised to on
the parent commit (3500235), before the program-set scaffolding was
lifted into models/served_lm.py."""
import hashlib
import json

import numpy as np
import pytest

from chipbench import reference_zaya as ref
from paddle_tpu.models import cca_moe, hybrid_ssm
from paddle_tpu.serving.generation import (GenerationConfig,
                                           GenerationModel,
                                           GenerationSpec)

ARCH = dict(hidden_size=64, head_dim=16, num_attention_heads=4,
            num_key_value_heads=2, cca_time0=2, cca_time1=2,
            layer_types=["hybrid"] * 3,
            rope_parameters={"hybrid": {"partial_rotary_factor": 0.5,
                                        "rope_theta": 5000000,
                                        "rope_type": "default"}},
            num_experts=4, num_experts_per_tok=1,
            moe_intermediate_size=32, router_hidden_size=16,
            rms_norm_eps=1e-5)
VOCAB, SLOTS = 96, 4


def _spec(dtype="float32", arch=ARCH, seed=0, **kw):
    family = dict(arch=arch, embedding_std=0.5,
                  dtypes=dict(weights=dtype, kv=dtype, conv=dtype))
    args = dict(vocab_size=VOCAB, max_seq_len=64, slots=SLOTS,
                prompt_buckets=[8, 32], cache_buckets=[32, 64], eos_id=-1,
                seed=seed, family="cca_moe", arch=family)
    args.update(kw)
    return GenerationSpec(**args)


@pytest.fixture(scope="module")
def model():
    return GenerationModel.build(_spec())


def _tape(m):
    lm = m.programs["prefill"][m.spec.prompt_buckets[0]]
    return [np.asarray(m.scope.get(p.name))
            for p in lm.main.all_parameters()]


def _logits_name(lm):
    ops = [o for o in lm.main.global_block().ops if o.type == "arg_max"]
    return ops[-1].input("X")[0]


def _fetch_logits(m, lm, feed):
    out = m.executor.run(lm.main, feed=feed,
                         fetch_list=[_logits_name(lm)], scope=m.scope)
    return np.asarray(out[0])


def _prefill_logits(m, prompt, slot):
    bucket = next(b for b in m.spec.prompt_buckets if b >= len(prompt))
    ids = np.zeros((1, bucket, 1), np.int64)
    ids[0, :len(prompt), 0] = prompt
    return _fetch_logits(m, m.programs["prefill"][bucket], {
        "token_ids": ids, "lengths": np.asarray([len(prompt)]),
        "slot": np.asarray([slot])}).reshape(-1)


def _decode_logits(m, token, position, slot, bucket=32):
    toks = np.zeros((SLOTS, 1, 1), np.int64)
    pos = np.zeros(SLOTS, np.int64)
    lens = np.zeros(SLOTS, np.int64)
    toks[slot, 0, 0], pos[slot], lens[slot] = token, position, position + 1
    return _fetch_logits(m, m.programs["decode"][bucket], {
        "token_ids": toks, "positions": pos, "lengths": lens})[slot] \
        .reshape(-1)


def _through_the_server(m, seq, n_prompt, slot, bucket=32):
    """Logits at positions n_prompt - 1 .. len(seq) - 1: a prefill of
    the first n_prompt tokens, then one decode step a token."""
    rows = [_prefill_logits(m, seq[:n_prompt], slot)]
    for t in range(n_prompt, len(seq)):
        rows.append(_decode_logits(m, seq[t], t, slot, bucket))
    return np.stack(rows)


# Program and reference are float32 on the CPU and differ by the order
# of their sums alone: logits of size ~1 agree to 1e-4 relative. A pick
# at a near-tie could differ and move a logit by more; the seeds below
# were checked to hold no margin under 1e-4.
TOL = dict(rtol=2e-4, atol=2e-5)


# -- logits against the reference -----------------------------------------

# a prompt shorter than its bucket (pad rows), one that fills it, one
# of a single token (the windows of an empty past), one in the second
# bucket
@pytest.mark.parametrize("n_prompt,total,slot", [
    (5, 20, 1), (8, 14, 0), (1, 9, 3), (19, 30, 2)])
def test_prefill_then_decode_logits_are_the_references_at_every_position(
        model, n_prompt, total, slot):
    seq = np.random.default_rng(total).integers(1, VOCAB, total)
    want = ref.logits(_tape(model), seq[None], ARCH)[0]
    got = _through_the_server(model, seq, n_prompt, slot)
    np.testing.assert_allclose(got, want[n_prompt - 1:], **TOL)


def test_logits_past_the_first_cache_bucket(model):
    seq = np.random.default_rng(7).integers(1, VOCAB, 40)
    want = ref.logits(_tape(model), seq[None], ARCH)[0]
    got = _through_the_server(model, seq, 30, 2, bucket=64)
    np.testing.assert_allclose(got, want[29:], **TOL)


def test_decode_through_the_row_major_kernel_gives_the_references_logits(
        monkeypatch):
    """The family at 128-wide key heads, as ZAYA1's, with its decode
    sites steered to the row-major body of the decode_attention kernel
    (interpret mode here; on a TPU the rule picks it by itself: a
    128-wide key is held row-major) and its appends to their kernel:
    two query heads a key head, one block of 128 positions."""
    from paddle_tpu.ops import cache_ops, nn_ops
    from tests.test_decode_attention_kernel import _sdpa_sites

    arch = dict(ARCH, head_dim=128)
    m = GenerationModel.build(_spec(arch=arch, max_seq_len=128,
                                    prompt_buckets=[8],
                                    cache_buckets=[128]))
    monkeypatch.setattr(nn_ops, "_decode_kernel_lane_axis",
                        lambda ctx, q, cache, bound: 3)
    monkeypatch.setattr(cache_ops, "_append_kernel_lane_axis",
                        lambda ctx, cache: 3)
    seq = np.random.default_rng(11).integers(1, VOCAB, 16)
    before = _sdpa_sites()
    got = _through_the_server(m, seq, 5, 1, bucket=128)
    decode_sites = {labels: n for labels, n in
                    (_sdpa_sites() - before).items() if labels[1] == "kv_len"}
    assert decode_sites == {
        ("decode_kernel", "kv_len", "0", "0", "2", "bhsd"):
        len(arch["layer_types"])}
    want = ref.logits(_tape(m), seq[None], arch)[0]
    np.testing.assert_allclose(got, want[4:], **TOL)


def test_full_program_gives_the_references_last_row(model):
    seq = np.random.default_rng(2).integers(1, VOCAB, 6)
    ids = np.zeros((SLOTS, 8, 1), np.int64)
    ids[2, :6, 0] = seq
    full = _fetch_logits(model, model._full(8), {
        "token_ids": ids, "lengths": np.asarray([1, 1, 6, 1])})
    want = ref.logits(_tape(model), seq[None], ARCH)[0, -1]
    np.testing.assert_allclose(full[2].reshape(-1), want, **TOL)


def test_the_reference_through_its_own_prefix_agrees_with_its_full_pass(
        model):
    """The reference has no cache to disagree with: a prefix's logits
    are the full pass's at those positions (causality), to the order of
    float32 sums."""
    seq = np.random.default_rng(3).integers(1, VOCAB, 24)
    tape = _tape(model)
    whole = ref.logits(tape, seq[None], ARCH)[0]
    np.testing.assert_allclose(ref.logits(tape, seq[None, :9], ARCH)[0],
                               whole[:9], **TOL)


def test_a_slot_reused_after_a_longer_request_gives_what_a_fresh_slot_gives(
        model):
    """A prefill overwrites both kinds of a slot's state: nothing is
    inherited from the request that held it before."""
    rng = np.random.default_rng(11)
    long_seq, short_seq = rng.integers(1, VOCAB, 28), \
        rng.integers(1, VOCAB, 10)
    _through_the_server(model, long_seq, 20, 1)        # slot 1 is dirty
    reused = _through_the_server(model, short_seq, 3, 1)
    fresh = _through_the_server(GenerationModel.build(_spec()),
                                short_seq, 3, 1)
    np.testing.assert_array_equal(reused, fresh)
    want = ref.logits(_tape(model), short_seq[None], ARCH)[0]
    np.testing.assert_allclose(reused, want[2:], **TOL)


def test_the_cache_the_windows_and_the_picks_are_what_the_reference_keeps(
        model):
    """A prefill of 7 (one pad row in its bucket of 8), then 13 decode
    steps: the slot's keys after the rotation, its values, its three
    windows and every layer's picks against the reference's own."""
    seq = np.random.default_rng(23).integers(1, VOCAB, 20)
    model.run_prefill(seq[:7].tolist(), 2)
    picks = [model.last_observed["picks"][:, :7]]
    assert (model.last_observed["picks"][:, 7:] == -1).all()
    for t in range(7, 20):
        last, pos, length = np.zeros((3, SLOTS), np.int64)
        last[2], pos[2], length[2] = seq[t], t, t + 1
        model.run_decode(last, pos, 32, length)
        picks.append(model.last_observed["picks"][:, 2:3])
        assert (np.delete(model.last_observed["picks"], 2, 1) == -1).all()
    picks = np.concatenate(picks, axis=1)                   # [layers, 20]
    for i, kept in enumerate(ref.states(_tape(model), seq[None], ARCH)):
        for which in "kv":
            ours = np.asarray(
                model.scope.get(f"kv_cache.l{i}.{which}"))[2, :, :20]
            np.testing.assert_allclose(ours, np.asarray(kept[which])[0],
                                       rtol=1e-4, atol=1e-5)
        for window, theirs in (("z", "z"), ("a", "a"), ("v", "v2")):
            np.testing.assert_allclose(
                np.asarray(model.scope.get(f"conv_state.l{i}.{window}"))[2],
                np.asarray(kept[theirs])[0], rtol=1e-4, atol=1e-5)
        assert kept["margin"].min() > 1e-4
        np.testing.assert_array_equal(picks[i], kept["pick"][0])


def test_slots_do_not_read_each_others_state(model):
    rng = np.random.default_rng(13)
    a, b = rng.integers(1, VOCAB, 12), rng.integers(1, VOCAB, 12)
    alone = _through_the_server(model, a, 4, 0)
    _prefill_logits(model, a[:4], 0)
    _prefill_logits(model, b[:4], 3)
    rows = []
    for t in range(4, 12):           # both slots in ONE decode step
        toks = np.zeros((SLOTS, 1, 1), np.int64)
        pos = np.zeros(SLOTS, np.int64)
        lens = np.zeros(SLOTS, np.int64)
        toks[0, 0, 0], toks[3, 0, 0] = a[t], b[t]
        pos[[0, 3]], lens[[0, 3]] = t, t + 1
        out = _fetch_logits(model, model.programs["decode"][32], {
            "token_ids": toks, "positions": pos, "lengths": lens})
        rows.append(out[0].reshape(-1))
    np.testing.assert_allclose(np.stack(rows), alone[1:], rtol=1e-5,
                               atol=1e-6)


# -- the engine, its counters ---------------------------------------------

def test_the_engine_serves_the_family_and_counts_the_live_rows():
    """Through the same engine as the other families; the expert
    counters read the live slots' rows — a layer a step as many rows as
    requests in flight — and never an empty slot's."""
    m = GenerationModel.build(_spec(seed=5))
    engine = m.serve(config=GenerationConfig(max_new_tokens=6)).start()
    try:
        futures = [engine.submit([1, 2, 3, 4 + i], 6) for i in range(2)]
        results = [f.result(timeout=120) for f in futures]
    finally:
        engine.stop()
    assert [len(r.tokens) for r in results] == [6, 6]
    stats = engine.stats()
    layers = len(ARCH["layer_types"])
    rows = sum(stats["expert_rows_by_expert"].values())
    # 2 requests x 5 decoded tokens (the first comes from the prefill),
    # whatever steps they shared: a row a layer each, of 4 slots
    assert rows == 2 * 5 * layers
    assert layers * stats["steps"] <= stats["experts_read"] <= rows
    # solo replay: tokens hang on the request, not on its neighbours
    again = GenerationModel.build(_spec(seed=5))
    tok = [again.run_prefill([1, 2, 3, 4], 0)]
    for t in range(5):
        last, pos, length = np.zeros((3, SLOTS), np.int64)
        last[0], pos[0], length[0] = tok[-1], 4 + t, 5 + t
        tok.append(int(again.run_decode(last, pos, 32, length)[0]))
    assert tok == results[0].tokens


def test_a_family_without_experts_reports_nothing():
    m = GenerationModel.build(GenerationSpec(
        vocab_size=50, max_seq_len=16, slots=2, prompt_buckets=(8, 16),
        cache_buckets=(16,), n_layer=1, n_head=2, d_model=16, d_inner=32))
    m.run_prefill([1, 2, 3], 0)
    assert m.last_observed == {} and m.last_expert_rows() is None


# -- storage ------------------------------------------------------------

def test_served_storage_is_bfloat16_but_for_the_scales_and_the_router():
    m = GenerationModel.build(_spec(dtype="bfloat16"))
    for names in m.state_kinds.values():
        assert {str(m.scope.get(n).dtype) for n in names} == {"bfloat16"}
    lm = m.programs["prefill"][8]
    by_dtype = {}
    for p in lm.main.all_parameters():
        by_dtype.setdefault(str(m.scope.get(p.name).dtype), []).append(
            p.name.split(".")[0].rstrip("0123456789_"))
    assert set(by_dtype) == {"bfloat16", "float32"}
    # float32: norm scales, tau, the router's arrays; nothing else
    assert set(by_dtype["float32"]) <= {"norm", "final_norm", "cca",
                                        "router"}
    assert "moe_experts" in by_dtype["bfloat16"]
    seq = np.random.default_rng(4).integers(1, VOCAB, 12)
    got = _through_the_server(m, seq, 5, 1)
    want = ref.logits(_tape(m), seq[None], ARCH)[0][4:]
    # bfloat16 activations, three layers deep: ~1e-2 of a logit's size
    assert np.abs(got - want).max() < 0.05 * np.abs(want).max()


def test_the_state_kinds_and_their_shapes():
    names = cca_moe.state_names(3)
    assert len(names["kv"]) == 6 and len(names["conv"]) == 9
    shapes = cca_moe._state_shapes(ARCH, SLOTS, 64, cca_moe.SERVED_DTYPES)
    assert shapes["kv_cache.l0.k"][0] == [SLOTS, 2, 64, 16]
    assert shapes["conv_state.l2.z"][0] == [SLOTS, 96]
    assert shapes["conv_state.l2.a"][0] == [SLOTS, 96]
    assert shapes["conv_state.l2.v"][0] == [SLOTS, 16]


def test_a_layer_type_the_family_does_not_know_is_refused():
    with pytest.raises(ValueError, match="layer_types"):
        cca_moe.build_cca_moe_lm(dict(ARCH, layer_types=["hybrid_sliding"]))


def test_the_spec_goes_through_save_and_load(tmp_path, model):
    model.save(str(tmp_path / "m"), model_version="v1")
    loaded = GenerationModel.load(str(tmp_path / "m"))
    assert loaded.spec == model.spec and loaded.spec.family == "cca_moe"
    prompt = [5, 6, 7, 8, 9]
    assert loaded.run_prefill(prompt, 0) == model.run_prefill(prompt, 0)


# -- the older families' programs, as on the parent ---------------------------

# sha256 (first 16 hex) of json.dumps(desc.to_dict(), sort_keys=True) of
# every program models/hybrid_ssm.py built at tests/test_hybrid_ssm.py's
# size (weights bfloat16) at commit 3500235, before served_lm.py
HYBRID_PINNED = {
    "prefill[8].main": "7ea396f82528b13f",
    "prefill[8].startup": "e45a903b6323bd03",
    "prefill[32].main": "c235d447bbb0e5f4",
    "prefill[32].startup": "e45a903b6323bd03",
    "decode[32].main": "4f10deeca671eba0",
    "decode[32].startup": "e45a903b6323bd03",
    "decode[64].main": "35c29587770ebafb",
    "decode[64].startup": "e45a903b6323bd03",
    "full[8].main": "37fec2906aa6d141",
    "full[8].startup": "f6cd26f26799b7c2",
    "full[32].main": "ab21a40fe9bd67d9",
    "full[32].startup": "f6cd26f26799b7c2"}


@pytest.fixture(scope="module")
def hybrid_programs():
    from tests.test_hybrid_ssm import ARCH as hybrid_arch
    return hybrid_ssm.build_hybrid_lm(
        hybrid_arch, vocab_size=96, max_seq_len=64, slots=4,
        prompt_buckets=(8, 32), cache_buckets=(32, 64), seed=0,
        dtypes=dict(weights="bfloat16"), embedding_std=0.02)


@pytest.mark.parametrize("which", sorted(HYBRID_PINNED))
def test_the_hybrid_familys_programs_serialise_as_on_the_parent(
        hybrid_programs, which):
    mode, rest = which.split("[")
    bucket, part = rest.split("].")
    lm = hybrid_programs[mode][int(bucket)]
    desc = (lm.main if part == "main" else lm.startup).desc
    digest = hashlib.sha256(json.dumps(
        desc.to_dict(), sort_keys=True).encode()).hexdigest()[:16]
    assert digest == HYBRID_PINNED[which]


# -- what the tracing books ---------------------------------------------------

def test_the_op_table_books_the_new_ops_under_their_own_types(model):
    """profiler.op_times reduces a device trace through a program's op
    table: the instructions of a decode step map to the family's ops by
    type, the router, the grouped convolution and the head norms among
    them."""
    from paddle_tpu.core.executor import compiled_programs
    last, pos, length = np.zeros((3, SLOTS), np.int64)
    last[1], pos[1], length[1] = 3, 4, 5
    model.run_decode(last, pos, 32, length)
    uid = model.programs["decode"][32].main.desc.uid
    entry = [e for e in compiled_programs() if e.uid == uid][-1]
    booked = {ref.op_type for ref in entry.op_table().ops.values()}
    assert {"mlp_router", "grouped_conv_state_update", "cca_qk_mix",
            "conv_state_update", "moe_experts", "rotary_embedding",
            "scaled_dot_product_attention"} <= booked
