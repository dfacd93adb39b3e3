"""The delta-rule / attention family (models/delta_hybrid.py) on the
token server: prefill-then-decode LOGITS against the plain reference
(chipbench/reference_olmo_hybrid.py: the recurrence as a scan over
positions, no chunks, no cache) at every position, through all three
kinds of per-slot state; slots reused; requests admitted at different
steps; the spec's family through save and load; and the two older
families' programs pinned to what they serialised to before
models/served_lm.py gained a head of its own."""
import hashlib
import json

import numpy as np
import pytest

from chipbench import reference_olmo_hybrid as ref
from paddle_tpu.models import delta_hybrid
from paddle_tpu.observability import default_registry
from paddle_tpu.serving.generation import (GenerationConfig,
                                           GenerationModel,
                                           GenerationSpec)

ARCH = dict(hidden_size=64, intermediate_size=128,
            layer_types=["linear_attention", "linear_attention",
                         "full_attention", "linear_attention"],
            num_attention_heads=4, num_key_value_heads=4,
            linear_num_key_heads=4, linear_num_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=32,
            linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
            rms_norm_eps=1e-6)
VOCAB, SLOTS = 96, 4


def _spec(dtype="float32", arch=ARCH, seed=0, **kw):
    family = dict(arch=arch, embedding_std=1.0,
                  dtypes=dict(weights=dtype, kv=dtype, conv=dtype))
    args = dict(vocab_size=VOCAB, max_seq_len=128, slots=SLOTS,
                prompt_buckets=[8, 32, 128], cache_buckets=[32, 128],
                eos_id=-1, seed=seed, family="delta_hybrid", arch=family)
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
    slots = m.spec.slots
    toks = np.zeros((slots, 1, 1), np.int64)
    pos = np.zeros(slots, np.int64)
    lens = np.zeros(slots, np.int64)
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


# -- logits against the reference -----------------------------------------

# float32 storage: the program's chunked form, its carried windows and
# its caches against a scan over positions; what is left is the order of
# float32 sums (the triangular solve against 64 sequential updates).
# A prompt shorter than its bucket (pad rows), one that fills it, one of
# a single token, one past the first bucket, one of exactly a chunk,
# one past a chunk's edge (65 of a bucket of 128: 63 pad rows)
@pytest.mark.parametrize("n_prompt,total,slot,bucket", [
    (5, 20, 1, 32), (8, 14, 0, 32), (1, 9, 3, 32), (19, 30, 2, 32),
    (64, 70, 1, 128), (65, 72, 3, 128)])
def test_prefill_then_decode_logits_are_the_references_at_every_position(
        model, n_prompt, total, slot, bucket):
    seq = np.random.default_rng(total).integers(1, VOCAB, total)
    want = ref.logits(_tape(model), seq[None], ARCH)[0]
    got = _through_the_server(model, seq, n_prompt, slot, bucket)
    np.testing.assert_allclose(got, want[n_prompt - 1:], rtol=2e-4,
                               atol=2e-5)


def test_another_stack_attention_first_and_positive_eigenvalues_only():
    arch = dict(ARCH, layer_types=["full_attention", "linear_attention",
                                   "linear_attention", "full_attention"],
                linear_allow_neg_eigval=False)
    m = GenerationModel.build(_spec(arch=arch, seed=3))
    seq = np.random.default_rng(1).integers(1, VOCAB, 18)
    want = ref.logits(_tape(m), seq[None], arch)[0]
    got = _through_the_server(m, seq, 6, 1)
    np.testing.assert_allclose(got, want[5:], rtol=2e-4, atol=2e-5)
    # the factor 2 is in the mathematics, not a label
    other = ref.logits(_tape(m), seq[None],
                       dict(arch, linear_allow_neg_eigval=True))[0]
    assert np.abs(other - want).max() > 1e-2


def test_full_program_gives_the_prefills_logits(model):
    seq = np.random.default_rng(2).integers(1, VOCAB, 6)
    ids = np.zeros((SLOTS, 8, 1), np.int64)
    ids[2, :6, 0] = seq
    lengths = np.asarray([1, 1, 6, 1])
    full = _fetch_logits(model, model.programs["full"][8],
                         {"token_ids": ids, "lengths": lengths})
    np.testing.assert_allclose(full[2].reshape(-1),
                               _prefill_logits(model, seq, 0), rtol=1e-5,
                               atol=1e-6)


def test_a_slot_reused_by_a_second_request_inherits_nothing(model):
    """A prefill overwrites all three kinds of a slot's state."""
    rng = np.random.default_rng(11)
    long_seq, short_seq = rng.integers(1, VOCAB, 28), \
        rng.integers(1, VOCAB, 10)
    _through_the_server(model, long_seq, 20, 1)        # slot 1 is dirty
    reused = _through_the_server(model, short_seq, 3, 1)
    fresh_model = GenerationModel.build(_spec())
    fresh = _through_the_server(fresh_model, short_seq, 3, 1)
    np.testing.assert_array_equal(reused, fresh)
    want = ref.logits(_tape(model), short_seq[None], ARCH)[0]
    np.testing.assert_allclose(reused, want[2:], rtol=2e-4, atol=2e-5)


def test_all_three_kinds_of_a_slots_state_are_what_the_reference_keeps(
        model):
    """A prefill of 7 (one pad row in its bucket of 8), then 13 decode
    steps: the slot's matrix states, windows and keys and values
    against the reference's own, a layer at a time."""
    seq = np.random.default_rng(23).integers(1, VOCAB, 20)
    _through_the_server(model, seq, 7, 2)
    for i, kept in enumerate(ref.states(_tape(model), seq[None], ARCH)):
        if ARCH["layer_types"][i] == "linear_attention":
            final, windows = kept
            ours = np.asarray(model.scope.get(f"delta_state.l{i}"))[2]
            # theirs [H, d_k, d_v] -> ours [d_k, H * d_v]
            np.testing.assert_allclose(
                ours, np.moveaxis(np.asarray(final)[0], 0, 1)
                .reshape(16, -1), rtol=2e-4, atol=2e-6)
            for which in "qkv":
                np.testing.assert_allclose(
                    np.asarray(model.scope.get(
                        f"conv_state.l{i}.{which}"))[2],
                    np.asarray(windows[which])[0].reshape(-1),
                    rtol=5e-4, atol=5e-6)
        else:
            for which, theirs in zip("kv", kept):
                ours = np.asarray(
                    model.scope.get(f"kv_cache.l{i}.{which}"))[2, :, :20]
                np.testing.assert_allclose(ours, np.asarray(theirs)[0],
                                           rtol=5e-4, atol=5e-6)


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


# -- storage ------------------------------------------------------------

def test_served_storage_is_bfloat16_but_for_the_state_and_the_scales():
    m = GenerationModel.build(_spec(dtype="bfloat16"))
    kinds = {k: {str(m.scope.get(n).dtype) for n in names}
             for k, names in m.state_kinds.items()}
    assert kinds == {"kv": {"bfloat16"}, "conv": {"bfloat16"},
                     "delta": {"float32"}}
    lm = m.programs["prefill"][8]
    for p in lm.main.all_parameters():
        have = str(m.scope.get(p.name).dtype)
        assert have == ("bfloat16" if len(p.shape) == 2 else "float32")
    # bfloat16 weights are exact in the float32 reference; what differs
    # is bfloat16 activations between ops, 2^-9 relative a rounding,
    # through 8 unit-RMS branches and a head of Xavier columns: a
    # hundredth of the logits' own spread, not a tenth
    seq = np.random.default_rng(5).integers(1, VOCAB, 16)
    want = ref.logits(_tape(m), seq[None], ARCH)[0]
    got = _through_the_server(m, seq, 6, 1)
    assert np.abs(got - want[5:]).max() < 0.05 * np.abs(want).max()
    sizes = m.state_bytes()
    assert sizes == {"kv": 2 * SLOTS * 4 * 128 * 16 * 2,
                     "conv": 3 * SLOTS * 3 * (64 + 64 + 128) * 2,
                     "delta": 3 * SLOTS * 16 * 128 * 4}


def test_state_names_by_kind_and_the_frozen_check():
    names = delta_hybrid.state_names(["linear_attention",
                                      "full_attention"])
    assert names == {"kv": ["kv_cache.l1.k", "kv_cache.l1.v"],
                     "conv": ["conv_state.l0.q", "conv_state.l0.k",
                              "conv_state.l0.v"],
                     "delta": ["delta_state.l0"]}
    programs = delta_hybrid.build_delta_hybrid_lm(
        ARCH, vocab_size=VOCAB, max_seq_len=32, slots=2,
        prompt_buckets=[8], cache_buckets=[32])
    # told that only KV caches may be written, the model refuses the
    # programs that write windows and matrix states
    programs["state_prefixes"] = ("kv_cache.",)
    with pytest.raises(ValueError, match="not frozen"):
        GenerationModel(programs, _spec(slots=2, max_seq_len=32,
                                        prompt_buckets=[8],
                                        cache_buckets=[32]))


@pytest.mark.parametrize("broken,match", [
    ({"layer_types": ["linear_attention", "mamba"]}, "layer_types"),
    ({"linear_num_key_heads": 2}, "one key head"),
    ({"rms_norm_eps": None}, "lacks"),
    ({"num_key_value_heads": 3}, "divide")])
def test_an_architecture_the_family_cannot_build_is_refused(broken, match):
    with pytest.raises(ValueError, match=match):
        delta_hybrid.build_delta_hybrid_lm(dict(ARCH, **broken),
                                           vocab_size=VOCAB)


def test_the_head_is_a_parameter_of_its_own_and_the_last(model):
    lm = model.programs["decode"][32]
    params = lm.main.all_parameters()
    assert params[-1].name.startswith("lm_head") \
        and list(params[-1].shape) == [64, VOCAB]
    assert list(params[0].shape) == [VOCAB, 64]
    assert not np.array_equal(np.asarray(model.scope.get(params[0].name)),
                              np.asarray(model.scope.get(
                                  params[-1].name)).T)


# -- through the engine ---------------------------------------------------

def test_requests_admitted_at_different_steps_give_the_tokens_each_gives_alone(
        model):
    rng = np.random.default_rng(17)
    jobs = [(rng.integers(1, VOCAB, n).tolist(), k)
            for n, k in ((3, 12), (9, 5), (5, 9), (14, 7), (2, 10), (7, 3))]

    def serve(batch):
        engine = model.serve(config=GenerationConfig(max_new_tokens=16),
                             mode="cached").start()
        try:
            futures = [engine.submit(p, k) for p, k in batch]
            return [f.result(timeout=120).tokens for f in futures]
        finally:
            engine.stop(drain=False, timeout=30)

    together = serve(jobs)             # 6 requests on 4 slots
    for job, tokens in zip(jobs, together):
        assert serve([job])[0] == tokens
        assert len(tokens) == job[1]


def test_the_engine_publishes_the_delta_states_bytes(model):
    engine = model.serve(config=GenerationConfig(max_new_tokens=4),
                         mode="cached")
    fam = default_registry().get("paddle_tpu_decode_state_bytes")
    mine = {labels[1]: child.value for labels, child in fam.samples()
            if labels[0] == engine.metrics.engine_label}
    assert mine == {k: float(v) for k, v in model.state_bytes().items()}
    assert mine["delta"] == 3 * SLOTS * 16 * 128 * 4
    engine.metrics.retire()


def test_the_site_counters_say_what_each_site_was_handed():
    fam_name = "paddle_tpu_delta_sites_total"

    def counts():
        fam = default_registry().get(fam_name)
        return {k: c.value for k, c in fam.samples()} if fam else {}

    before = counts()
    m = GenerationModel.build(_spec(slots=2, max_seq_len=32,
                                    prompt_buckets=[8],
                                    cache_buckets=[32], seed=5))
    m.run_prefill([1, 2, 3], 0)
    m.run_decode(np.ones(2, np.int64), np.asarray([3, 0]), 32)
    after = counts()
    new = {k: after[k] - before.get(k, 0) for k in after
           if after[k] != before.get(k, 0)}
    # three linear layers: one chunked site a prefill program, one
    # composed site a decode program (no TPU here)
    assert new == {("gated_delta_prefill", "chunked", "64"): 3,
                   ("gated_delta_state_update", "composed", "0"): 3}


# -- the spec's family ------------------------------------------------------

def test_a_delta_hybrid_spec_round_trips_through_a_dict():
    spec = _spec(dtype="bfloat16")
    d = json.loads(json.dumps(spec.to_dict()))
    again = GenerationSpec.from_dict(d)
    assert again == spec and again.family == "delta_hybrid"
    assert again.arch["arch"]["layer_types"] == ARCH["layer_types"]


def test_save_and_load_rebuild_the_family_the_spec_names(tmp_path):
    spec = _spec(slots=2, max_seq_len=32, prompt_buckets=[8],
                 cache_buckets=[32])
    m = GenerationModel.build(spec)
    prompt = [5, 9, 2, 7]
    first = m.run_prefill(prompt, 1)
    m.save(str(tmp_path / "delta"), model_version="v1")
    again = GenerationModel.load(str(tmp_path / "delta"))
    assert again.spec == spec and again.version == "v1"
    assert sorted(again.state_kinds) == sorted(m.state_kinds)
    assert again.run_prefill(prompt, 0) == first


# -- the two older families' programs, as before --------------------------

def _digests(programs, buckets):
    out = {}
    for mode, bucket in buckets:
        lm = programs[mode][bucket]
        for part in ("main", "startup"):
            desc = getattr(lm, part).desc
            out[f"{mode}[{bucket}].{part}"] = hashlib.sha256(json.dumps(
                desc.to_dict(), sort_keys=True).encode()).hexdigest()[:16]
    return out


BUCKETS = (("prefill", 8), ("decode", 32), ("full", 8))
# sha256 (first 16 hex) of json.dumps(desc.to_dict(), sort_keys=True) of
# the programs each family built at tests/test_hybrid_ssm.py's and
# tests/test_cca_moe.py's toy sizes at commit d867aea, before
# models/served_lm.py gained ``untied_head``
PINNED = {
    "hybrid_ssm": {
        "prefill[8].main": "7ea396f82528b13f",
        "prefill[8].startup": "e45a903b6323bd03",
        "decode[32].main": "4f10deeca671eba0",
        "decode[32].startup": "e45a903b6323bd03",
        "full[8].main": "37fec2906aa6d141",
        "full[8].startup": "f6cd26f26799b7c2"},
    "cca_moe": {
        "prefill[8].main": "220aba840143ac20",
        "prefill[8].startup": "7a264e7ad0f0b4b1",
        "decode[32].main": "0f28db03419107c1",
        "decode[32].startup": "7a264e7ad0f0b4b1",
        "full[8].main": "c4f2e8acff79c52d",
        "full[8].startup": "b48f25a3b4aa2d89"}}


def _older_family(family):
    if family == "hybrid_ssm":
        from paddle_tpu.models.hybrid_ssm import build_hybrid_lm
        from tests.test_hybrid_ssm import ARCH as arch
        return build_hybrid_lm(arch, vocab_size=96, max_seq_len=64,
                               slots=4, prompt_buckets=[8, 32],
                               cache_buckets=[32, 64])
    from paddle_tpu.models.cca_moe import build_cca_moe_lm
    from tests.test_cca_moe import ARCH as arch
    return build_cca_moe_lm(arch, vocab_size=96, max_seq_len=64, slots=4,
                            prompt_buckets=[8, 32], cache_buckets=[32, 64])


@pytest.fixture(scope="module")
def older_programs():
    return {f: _digests(_older_family(f), BUCKETS)
            for f in ("hybrid_ssm", "cca_moe")}


@pytest.mark.parametrize("which", [
    f"{family}:{mode}[{bucket}].{part}"
    for family in ("hybrid_ssm", "cca_moe") for mode, bucket in BUCKETS
    for part in ("main", "startup")])
def test_the_older_families_programs_serialise_to_the_parents_bytes(
        older_programs, which):
    family, name = which.split(":")
    assert older_programs[family][name] == PINNED[family][name]
