"""The hybrid state-space / attention family (models/hybrid_ssm.py) on
the token server: prefill-then-decode LOGITS against the plain reference
(chipbench/reference_granite.py) at every position, through all three
kinds of per-slot state; slots reused; requests admitted at different
steps; the spec's family through save and load; and the transformer
family's programs pinned to what they serialised to before a spec could
name a family."""
import hashlib
import json

import numpy as np
import pytest

from chipbench import reference_granite as ref
from paddle_tpu.models import hybrid_ssm
from paddle_tpu.models.transformer import build_decoder_lm
from paddle_tpu.observability import default_registry
from paddle_tpu.serving.generation import (GenerationConfig,
                                           GenerationModel,
                                           GenerationSpec)

ARCH = dict(hidden_size=64, intermediate_size=128,
            layer_types=["mamba", "attention", "mamba"],
            num_attention_heads=8, num_key_value_heads=2, mamba_n_heads=4,
            mamba_d_head=32, mamba_d_state=16, mamba_d_conv=4,
            mamba_chunk_size=8, mamba_n_groups=1, embedding_multiplier=12,
            residual_multiplier=0.22, attention_multiplier=0.2,
            logits_scaling=8, rms_norm_eps=1e-5)
VOCAB, SLOTS = 96, 4


def _spec(dtype="float32", arch=ARCH, seed=0, **kw):
    family = dict(arch=arch, embedding_std=0.02,
                  dtypes=dict(weights=dtype, kv=dtype, conv=dtype))
    args = dict(vocab_size=VOCAB, max_seq_len=64, slots=SLOTS,
                prompt_buckets=[8, 32], cache_buckets=[32, 64], eos_id=-1,
                seed=seed, family="hybrid_ssm", arch=family)
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


# -- logits against the reference -----------------------------------------

# a prompt shorter than its bucket (pad rows), one that fills it, one
# of a single token, one past the first chunk and bucket
@pytest.mark.parametrize("n_prompt,total,slot", [
    (5, 20, 1), (8, 14, 0), (1, 9, 3), (19, 30, 2)])
def test_prefill_then_decode_logits_are_the_references_at_every_position(
        model, n_prompt, total, slot):
    seq = np.random.default_rng(total).integers(1, VOCAB, total)
    want = ref.logits(_tape(model), seq[None], ARCH)[0]
    got = _through_the_server(model, seq, n_prompt, slot)
    np.testing.assert_allclose(got, want[n_prompt - 1:], rtol=1e-4,
                               atol=2e-6)


def test_logits_past_the_first_cache_bucket(model):
    seq = np.random.default_rng(7).integers(1, VOCAB, 40)
    want = ref.logits(_tape(model), seq[None], ARCH)[0]
    got = _through_the_server(model, seq, 30, 2, bucket=64)
    np.testing.assert_allclose(got, want[29:], rtol=1e-4, atol=2e-6)


def test_another_stack_mamba_only_and_attention_first():
    arch = dict(ARCH, layer_types=["attention", "mamba", "mamba",
                                   "attention"], mamba_chunk_size=4)
    m = GenerationModel.build(_spec(arch=arch, seed=3))
    seq = np.random.default_rng(1).integers(1, VOCAB, 18)
    want = ref.logits(_tape(m), seq[None], arch)[0]
    got = _through_the_server(m, seq, 6, 1)
    np.testing.assert_allclose(got, want[5:], rtol=1e-4, atol=2e-6)


def test_full_program_gives_the_prefills_logits(model):
    seq = np.random.default_rng(2).integers(1, VOCAB, 6)
    ids = np.zeros((SLOTS, 8, 1), np.int64)
    ids[2, :6, 0] = seq
    lengths = np.asarray([1, 1, 6, 1])
    full = _fetch_logits(model, model.programs["full"][8],
                         {"token_ids": ids, "lengths": lengths})
    np.testing.assert_allclose(full[2].reshape(-1),
                               _prefill_logits(model, seq, 0), rtol=1e-5,
                               atol=1e-7)


def test_a_slot_reused_by_a_shorter_request_gives_what_a_fresh_slot_gives(
        model):
    """A prefill overwrites all three kinds of a slot's state: nothing
    is inherited from the request that held it before."""
    rng = np.random.default_rng(11)
    long_seq, short_seq = rng.integers(1, VOCAB, 28), \
        rng.integers(1, VOCAB, 10)
    _through_the_server(model, long_seq, 20, 1)        # slot 1 is dirty
    reused = _through_the_server(model, short_seq, 3, 1)
    fresh_model = GenerationModel.build(_spec())
    fresh = _through_the_server(fresh_model, short_seq, 3, 1)
    np.testing.assert_array_equal(reused, fresh)
    want = ref.logits(_tape(model), short_seq[None], ARCH)[0]
    np.testing.assert_allclose(reused, want[2:], rtol=1e-4, atol=2e-6)


def test_all_three_kinds_of_a_slots_state_are_what_the_reference_keeps(
        model):
    """A prefill of 7 (one pad row in its bucket of 8), then 13 decode
    steps: the slot's recurrent states, windows and keys and values
    against the reference's own, a layer at a time."""
    seq = np.random.default_rng(23).integers(1, VOCAB, 20)
    _through_the_server(model, seq, 7, 2)
    for i, kept in enumerate(ref.states(_tape(model), seq[None], ARCH)):
        if ARCH["layer_types"][i] == "mamba":
            final, window = (np.asarray(a) for a in kept)
            ours = np.asarray(model.scope.get(f"ssm_state.l{i}"))[2]
            np.testing.assert_allclose(
                ours, np.moveaxis(final[0], 2, 0).reshape(16, -1),
                rtol=2e-4, atol=1e-6)
            np.testing.assert_allclose(
                np.asarray(model.scope.get(f"conv_state.l{i}"))[2],
                window[0].reshape(-1), rtol=1e-4, atol=1e-6)
        else:
            for which, theirs in zip("kv", kept):
                ours = np.asarray(
                    model.scope.get(f"kv_cache.l{i}.{which}"))[2, :, :20]
                np.testing.assert_allclose(ours, np.asarray(theirs)[0],
                                           rtol=1e-4, atol=1e-6)


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
                               atol=1e-7)


# -- storage ------------------------------------------------------------

def test_served_storage_is_bfloat16_but_for_the_state_and_the_scales():
    m = GenerationModel.build(_spec(dtype="bfloat16"))
    kinds = {k: {str(m.scope.get(n).dtype) for n in names}
             for k, names in m.state_kinds.items()}
    assert kinds == {"kv": {"bfloat16"}, "conv": {"bfloat16"},
                     "ssm": {"float32"}}
    lm = m.programs["prefill"][8]
    for p in lm.main.all_parameters():
        have = str(m.scope.get(p.name).dtype)
        assert have == ("bfloat16" if len(p.shape) == 2 else have)
        assert have in ("bfloat16", "float32")
    # bfloat16 weights are exact in the float32 reference; activations
    # and products differ
    seq = np.random.default_rng(5).integers(1, VOCAB, 16)
    want = ref.logits(_tape(m), seq[None], ARCH)[0]
    got = _through_the_server(m, seq, 6, 1)
    assert np.abs(got - want[5:]).max() < 0.05 * np.abs(want).max()
    sizes = m.state_bytes()
    assert sizes == {"kv": 2 * SLOTS * 2 * 64 * 8 * 2,
                     "conv": 2 * SLOTS * 3 * 160 * 2,
                     "ssm": 2 * SLOTS * 16 * 128 * 4}


def test_state_names_by_kind_and_the_frozen_check():
    names = hybrid_ssm.state_names(["mamba", "attention"])
    assert names == {"kv": ["kv_cache.l1.k", "kv_cache.l1.v"],
                     "conv": ["conv_state.l0"], "ssm": ["ssm_state.l0"]}
    programs = hybrid_ssm.build_hybrid_lm(
        ARCH, vocab_size=VOCAB, max_seq_len=32, slots=2,
        prompt_buckets=[8], cache_buckets=[32])
    # told that only KV caches may be written, the model refuses the
    # programs that write windows and recurrent states
    programs["state_prefixes"] = ("kv_cache.",)
    with pytest.raises(ValueError, match="not frozen"):
        GenerationModel(programs, _spec(slots=2, max_seq_len=32,
                                        prompt_buckets=[8],
                                        cache_buckets=[32]))


@pytest.mark.parametrize("broken,match", [
    ({"layer_types": ["mamba", "conv"]}, "layer_types"),
    ({"mamba_n_groups": 2}, "one group"),
    ({"rms_norm_eps": None}, "lacks"),
    ({"num_key_value_heads": 3}, "divide")])
def test_an_architecture_the_family_cannot_build_is_refused(broken, match):
    with pytest.raises(ValueError, match=match):
        hybrid_ssm.build_hybrid_lm(dict(ARCH, **broken), vocab_size=VOCAB)


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
    fam = default_registry().get("paddle_tpu_decode_state_bytes")
    kinds = {labels[1]: child.value for labels, child in fam.samples()}
    assert kinds == {k: float(v) for k, v in model.state_bytes().items()}


# -- the spec's family ------------------------------------------------------

def test_a_spec_without_family_is_a_transformer_and_says_no_more():
    spec = GenerationSpec(vocab_size=50, max_seq_len=16, slots=2)
    assert spec.family == "transformer" and spec.arch is None
    d = spec.to_dict()
    assert "family" not in d and "arch" not in d
    assert GenerationSpec.from_dict(d) == spec
    with pytest.raises(ValueError, match="family"):
        GenerationSpec(vocab_size=50, max_seq_len=16, family="lstm")


def test_a_hybrid_spec_round_trips_through_a_dict():
    spec = _spec(dtype="bfloat16")
    d = json.loads(json.dumps(spec.to_dict()))
    again = GenerationSpec.from_dict(d)
    assert again == spec and again.family == "hybrid_ssm"
    assert again.arch["arch"]["layer_types"] == ARCH["layer_types"]


@pytest.mark.parametrize("family", ["transformer", "hybrid_ssm"])
def test_save_and_load_rebuild_the_family_the_spec_names(tmp_path, family):
    if family == "hybrid_ssm":
        spec = _spec(slots=2, max_seq_len=32, prompt_buckets=[8],
                     cache_buckets=[32])
    else:
        spec = GenerationSpec(vocab_size=VOCAB, max_seq_len=32, slots=2,
                              prompt_buckets=[8], cache_buckets=[32],
                              n_layer=1, n_head=2, d_model=32, d_inner=64,
                              eos_id=-1)
    m = GenerationModel.build(spec)
    prompt = [5, 9, 2, 7]
    first = m.run_prefill(prompt, 1)
    m.save(str(tmp_path / family), model_version="v1")
    again = GenerationModel.load(str(tmp_path / family))
    assert again.spec == spec and again.version == "v1"
    assert sorted(again.state_kinds) == sorted(m.state_kinds)
    assert again.run_prefill(prompt, 0) == first


def test_re_forward_programs_are_built_when_first_asked_for(monkeypatch):
    """The served path never runs one: a build holds none, the first
    ``run_full`` of a bucket builds that bucket's and puts it through
    the constructor's two gates, the second finds it."""
    m = GenerationModel.build(_spec(slots=2, max_seq_len=32,
                                    prompt_buckets=[8, 16],
                                    cache_buckets=[32]))
    assert dict(m.programs["full"]) == {}
    assert sorted(m.programs["prefill"]) == [8, 16]
    gated = []
    for gate in ("_check_frozen", "_verify"):
        monkeypatch.setattr(
            m, gate, lambda programs, gate=gate: gated.append(
                (gate, [(mode, b) for mode, b, _lm in programs])))
    tokens = np.ones((2, 8), np.int64)
    first = m.run_full(tokens, np.asarray([3, 5]), 8)
    assert gated == [("_check_frozen", [("full", 8)]),
                     ("_verify", [("full", 8)])]
    assert list(m.programs["full"]) == [8]
    assert (m.run_full(tokens, np.asarray([3, 5]), 8) == first).all()
    assert len(gated) == 2
    with pytest.raises(KeyError):
        m.programs["full"][12]


def test_a_re_forward_program_that_writes_a_weight_is_refused_when_asked_for(
        monkeypatch):
    m = GenerationModel.build(_spec(slots=2, max_seq_len=32,
                                    prompt_buckets=[8], cache_buckets=[32]))
    monkeypatch.setattr(m, "_state_prefixes", ("no_such_state.",))
    # the prefill program writes its slot's state: under prefixes that
    # admit none, the same walk refuses it
    with pytest.raises(ValueError, match="not frozen"):
        m._check_frozen([("prefill", 8, m.programs["prefill"][8])])
    m._full(8)                    # a re-forward program writes no state


# -- the transformer family's programs, as before -----------------------------

# sha256 (first 16 hex) of json.dumps(desc.to_dict(), sort_keys=True) of
# every program build_decoder_lm made at this size at commit 381d7a8,
# before GenerationSpec had a family
PINNED = {
    "prefill[8].main": "e6ed6a1100a8e9f1",
    "prefill[8].startup": "75dbe85285cc5d6b",
    "prefill[32].main": "d9ebdbae47a70fd5",
    "prefill[32].startup": "75dbe85285cc5d6b",
    "decode[16].main": "77d74eafd3de6753",
    "decode[16].startup": "75dbe85285cc5d6b",
    "decode[32].main": "06c04828c0ba5693",
    "decode[32].startup": "75dbe85285cc5d6b",
    "full[8].main": "f740e0bf007eaafd",
    "full[8].startup": "736b05c3dcaf208a",
    "full[32].main": "c50a972ca1686110",
    "full[32].startup": "736b05c3dcaf208a"}


@pytest.fixture(scope="module")
def decoder_lm_programs():
    spec = GenerationSpec(vocab_size=96, max_seq_len=32, slots=4,
                          prompt_buckets=(8, 32), cache_buckets=(16, 32),
                          n_layer=2, n_head=2, d_model=32, d_inner=64,
                          seed=0)
    from paddle_tpu.serving.generation.model import FAMILIES
    return FAMILIES[spec.family](spec)


@pytest.mark.parametrize("which", sorted(PINNED))
def test_decoder_lm_programs_serialise_to_the_parents_bytes(
        decoder_lm_programs, which):
    mode, rest = which.split("[")
    bucket, part = rest.split("].")
    lm = decoder_lm_programs[mode][int(bucket)]
    desc = (lm.main if part == "main" else lm.startup).desc
    digest = hashlib.sha256(json.dumps(
        desc.to_dict(), sort_keys=True).encode()).hexdigest()[:16]
    assert digest == PINNED[which]


def test_the_family_table_builds_what_build_decoder_lm_builds():
    direct = build_decoder_lm(vocab_size=50, max_seq_len=16, slots=2,
                              prompt_buckets=(8, 16), cache_buckets=(16,),
                              n_layer=1, n_head=2, d_model=16, d_inner=32)
    m = GenerationModel.build(GenerationSpec(
        vocab_size=50, max_seq_len=16, slots=2, prompt_buckets=(8, 16),
        cache_buckets=(16,), n_layer=1, n_head=2, d_model=16, d_inner=32))
    assert m.cache_names == direct["cache_names"]
    assert m.state_kinds == {"kv": direct["cache_names"]}
    assert set(m.state_bytes()) == {"kv"}
