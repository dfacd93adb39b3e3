"""The ``granite-4p0-h-micro`` configuration and its cell
``granite-4p0-h-micro.serve-sessions``: the manifest's entries (found
BY NAME, wherever later PRs put theirs), the configuration file against
the published keys, the closed forms of chipbench/arith_granite.py at
the published sizes, the plain reference against the quadratic form,
the four readers on made-up runs, the driver's storage check and the
rehearsal of the cell."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import chipbench
from chipbench import arith_granite as arith
from chipbench import reference_granite as ref
from chipbench.manifest import Manifest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    chipbench.__file__)))
CELL = "granite-4p0-h-micro.serve-sessions"
CONFIG = "granite-4p0-h-micro"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_READERS = ("ssm_update_time_share_pct.serve",
               "ssm_update_roofline_pct.serve",
               "prefill_time_share_pct.serve", "step_mfu_pct.serve")


@pytest.fixture(scope="module")
def manifest():
    return Manifest(REPO)


@pytest.fixture(scope="module")
def cfg(manifest):
    return manifest.load_config(CONFIG)


def _metric(manifest, name):
    found = [m for group in ("end_to_end", "per_layer")
             for m in manifest.data[group] if m["name"] == name]
    assert len(found) == 1, name
    return found[0]


# -- the manifest ---------------------------------------------------------

def test_the_manifest_is_sound_with_the_cell_in_it(manifest):
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == "serve-sessions"
    entry = manifest.config_entry(CONFIG)
    assert entry["reduced"] == []
    assert entry["file"] == "chipbench/configs/granite-4p0-h-micro.json"
    assert entry["source"].startswith(
        "https://huggingface.co/ibm-granite/granite-4.0-h-micro/")


@pytest.mark.parametrize("name", [
    "serve_tokens_per_s", "tpot_ms_p95", "decode_step_ms.serve",
    "kv_live_share_pct.serve", "engine_host_ms.serve", *NEW_READERS])
def test_the_cell_is_on_the_lists_it_reports(manifest, name):
    assert CELL in _metric(manifest, name)["workloads"]


@pytest.mark.parametrize("name", [
    "ttft_ms_p95", "queue_wait_ms_p95.serve", "engine_queue_ms_p95.serve",
    "first_token_ms_p95.serve"])
def test_time_to_first_token_is_not_among_the_cells_metrics(manifest,
                                                            name):
    """While prefill is serial inside admission a p95 of time to first
    token is a queue's: printed in the notes, not judged."""
    assert CELL not in _metric(manifest, name)["workloads"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_lists_this_cell_alone_and_moves_tpot(manifest, name):
    m = _metric(manifest, name)
    assert m["workloads"] == [CELL] and m["moves"] == "tpot_ms_p95"
    assert m["unit"] == "%"
    assert m in manifest.metrics_for(CELL, "per_layer")
    assert manifest.load_reader(name).__doc__    # says what it reads


def test_the_cells_file_gives_the_issues_traffic(manifest):
    w = manifest.load_workload(CELL)
    t = w["traffic"]
    assert w["kind"] == "serve_state" and t["slots"] == 64
    assert t["queue_capacity"] == 1024 and t["ramp_s"] == 10.0
    assert t["prompt_len"] == {"median": 98, "sigma": 1.0, "min": 4,
                               "max": 1792}
    assert t["answer_len"] == {"median": 245, "sigma": 0.8, "min": 16,
                               "max": 1024}
    serve_chat = manifest.load_workload("decoder-lm-base.serve-chat")
    assert t["base_seed"] != serve_chat["traffic"]["base_seed"]
    assert t["prompt_len"] == serve_chat["traffic"]["prompt_len"]
    assert 0 < t["rate_per_s"] < 64 / (0.020 * 338)   # under the bytes' cap


# -- the configuration ------------------------------------------------------

PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


def test_the_configuration_holds_every_published_key_unchanged(cfg):
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    kinds = cfg["layer_types"]
    assert len(kinds) == 40
    assert [i for i, k in enumerate(kinds) if k == "attention"] == \
        [5, 15, 25, 35]
    assert set(kinds) == {"mamba", "attention"}
    assert cfg["reduced"] == []
    assert cfg["builder"]["args"]["vocab_size"] == cfg["vocab_size"]
    for key in ("gate_before_norm", "dt_clamp", "initialisers",
                "embedding_std", "decoding", "state_dtype"):
        assert cfg["assumed"][key]
    assert cfg["storage_dtypes"] == {
        "weights": "bfloat16", "kv": "bfloat16", "conv": "bfloat16",
        "ssm": "float32", "scales": "float32"}


def test_the_driver_builds_the_spec_from_the_published_keys(cfg):
    from chipbench.drivers import serve_state, sizes
    from paddle_tpu.models.hybrid_ssm import ARCH_KEYS
    args, _ = sizes(cfg, {"traffic": {}}, rehearse=False)
    spec = serve_state.build_spec(cfg, args, 64, rehearse=False)
    assert spec.family == "hybrid_ssm" and spec.slots == 64
    assert spec.arch["arch"] == {k: cfg[k] for k in ARCH_KEYS}
    assert spec.arch["dtypes"] == cfg["storage_dtypes"]
    assert spec.prompt_buckets == [128, 512, 2048]
    assert spec.cache_buckets == [1024, 4096] and spec.eos_id == -1
    toy = serve_state.build_spec(
        cfg, sizes(cfg, {"traffic": {}}, rehearse=True)[0], 4, True)
    assert toy.arch["arch"]["hidden_size"] == 64
    assert toy.arch["arch"]["residual_multiplier"] == 0.22


# -- the closed forms, at the published sizes ---------------------------------

def test_parameters_by_layer_and_in_all(cfg):
    assert arith.layer_params("mamba", **cfg) == 76_182_976
    assert arith.layer_params("attention", **cfg) == 60_821_504
    assert arith.model_params(**cfg) == 3_191_396_096
    # the in-projection alone: 2048 x (4096 + 4352 + 64)
    assert 2048 * 8512 == 17_432_576


def test_stored_bytes_of_weights_and_state(cfg):
    assert round(arith.weight_bytes(**cfg) / 1e9, 2) == 6.38
    state = arith.state_bytes(64, 4096, **cfg)
    assert round(state["ssm"] / 1e9, 2) == 4.83
    assert round(state["kv"] / 1e9, 2) == 2.15
    assert round(state["conv"] / 1e9, 2) == 0.06
    total = arith.weight_bytes(**cfg) + sum(state.values())
    assert round(total / 1e9, 1) == 13.4


def test_a_decode_steps_bytes_are_mostly_state(cfg):
    step = arith.decode_step_bytes(64, 64 * 400, **cfg)
    assert step["ssm"] == 2 * arith.state_bytes(64, 1, **cfg)["ssm"]
    assert round(step["kv_live"] / 1e9, 2) == 0.21
    assert 0.58 < step["ssm"] / step["total"] < 0.60
    assert 19.5 < step["total"] / PEAKS["hbm_bytes_per_s"] * 1e3 < 20.5


def test_one_state_update_is_bound_by_its_bytes(cfg):
    cost = arith.ssm_update_cost(64, **cfg)
    state = 64 * 4096 * 128
    assert cost["flops"] == 5 * state
    assert cost["bytes"] == 4 * (2 * state + 3 * 64 * 4096 + 2 * 64 * 128)
    least = arith.ssm_update_seconds(64, PEAKS, **cfg)
    assert least["bound"] == "bandwidth"
    assert 0.32e-3 < least["seconds"] < 0.34e-3


def test_model_flops_of_a_token_and_of_a_prompt(cfg):
    matrices = 36 * (76_182_976 - 30_144) + 4 * (60_821_504 - 4096)
    token = arith.decode_token_flops(0, **cfg)
    scans = 36 * (5 * 4096 * 128 + 2 * 4 * 4352)
    assert token == 2 * matrices + scans + 2 * 2048 * 100352
    # the scan is a small share of a layer's FLOPs, at any length
    assert scans / 36 / (2 * 76_152_832) < 0.02
    assert arith.decode_token_flops(1000, **cfg) - token == \
        4 * 32 * 4 * 64 * 1000
    n = 161
    assert arith.prefill_flops(n, **cfg) == \
        n * (2 * matrices + scans) + 4 * 32 * 4 * 64 * n * (n + 1) // 2 \
        + 2 * 2048 * 100352


# -- the reference ----------------------------------------------------------

def _toy_arch():
    return dict(mamba_n_heads=2, mamba_d_head=4, mamba_d_state=8,
                mamba_d_conv=4, num_attention_heads=4,
                num_key_value_heads=2, attention_multiplier=0.3,
                residual_multiplier=0.22, rms_norm_eps=1e-5,
                logits_scaling=8, embedding_multiplier=12,
                layer_types=["mamba", "attention"])


def _toy_weights(d=16, seed=0):
    rng = np.random.default_rng(seed)
    inner, n, heads = 8, 8, 2

    def mat(*shape):
        return rng.normal(0, 0.3, shape).astype(np.float32)

    mamba = dict(norm1=np.ones(d, np.float32), conv_w=mat(4, inner + 2 * n),
                 conv_b=mat(inner + 2 * n), a_log=mat(heads),
                 dt_bias=mat(heads), d=mat(heads),
                 w_in=mat(d, 2 * inner + 2 * n + heads),
                 norm_g=np.ones(inner, np.float32), w_out=mat(inner, d),
                 norm2=np.ones(d, np.float32), gate=mat(d, 24),
                 up=mat(d, 24), down=mat(24, d))
    attention = dict(norm1=np.ones(d, np.float32), w_q=mat(d, d),
                     w_k=mat(d, 8), w_v=mat(d, 8), w_o=mat(d, d),
                     norm2=np.ones(d, np.float32), gate=mat(d, 24),
                     up=mat(d, 24), down=mat(24, d))
    tape = [mat(20, d)] + [mamba[k] for k in ref.MAMBA_ARRAYS] \
        + [attention[k] for k in ref.ATTENTION_ARRAYS] \
        + [np.ones(d, np.float32)]
    return tape, mamba, attention


def test_the_references_recurrence_is_the_quadratic_form():
    """One position at a time under lax.scan against every pair of rows
    at once, in numpy float64."""
    import jax.numpy as jnp
    arch = _toy_arch()
    _, w, _ = _toy_weights()
    u = np.random.default_rng(1).normal(0, 1, (2, 9, 16)).astype(np.float32)
    out, final, _ = ref.mamba_mixer(
        jnp.asarray(u), {k: jnp.asarray(v) for k, v in w.items()}, arch)
    # the same, by hand
    inner, n, heads, width = 8, 8, 2, 4
    proj = u.astype(np.float64) @ w["w_in"]
    z, xbc, dt = np.split(proj, [inner, 2 * inner + 2 * n], -1)
    padded = np.pad(xbc, ((0, 0), (3, 0), (0, 0)))
    conv = w["conv_b"] + sum(w["conv_w"][k] * padded[:, k:k + 9]
                             for k in range(4))
    act = conv / (1 + np.exp(-conv))
    x, b, c = np.split(act, [inner, inner + n], -1)
    x = x.reshape(2, 9, heads, width)
    dt = np.log1p(np.exp(dt + w["dt_bias"]))
    cum = np.cumsum(dt * -np.exp(w["a_log"]), axis=1)
    seen = np.tril(np.ones((9, 9), bool))[None, :, :, None]
    decay = np.where(seen, np.exp(np.where(
        seen, cum[:, :, None] - cum[:, None], 0)), 0)
    weights = np.einsum("zin,zjn->zij", c, b)[..., None] * decay \
        * dt[:, None]
    y = np.einsum("zijh,zjhp->zihp", weights, x) + w["d"][:, None] * x
    g = y.reshape(2, 9, inner) * (z / (1 + np.exp(-z)))
    want = g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5) \
        @ w["w_out"]
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-5)
    assert final.shape == (2, heads, width, n)


def test_the_reference_reads_its_tape_by_layer_kind_and_counts_it():
    tape, mamba, attention = _toy_weights()
    table, layers, final = ref.layers_of(tape, ["mamba", "attention"])
    assert table is tape[0] and final is tape[-1]
    assert layers[0]["w_in"] is mamba["w_in"]
    assert layers[1]["w_o"] is attention["w_o"]
    with pytest.raises(ValueError, match="tape"):
        ref.layers_of(tape, ["mamba", "mamba"])


def test_choice_gaps_are_zero_on_the_references_own_greedy_tokens():
    arch = _toy_arch()
    tape, _, _ = _toy_weights(seed=3)
    seq = [3, 7]
    for _ in range(6):          # greedy continuation by the reference
        seq.append(int(ref.logits(tape, np.asarray([seq]), arch)[0, -1]
                       .argmax()))
    tokens = np.asarray([seq, seq])
    tokens[1, 5] = (tokens[1, 5] + 1) % 20          # one other choice
    gaps = ref.choice_gaps(tape, [tokens], arch)[0]
    assert gaps.shape == (2, 8)
    np.testing.assert_array_equal(gaps[0, 1:7], 0.0)
    assert gaps[1, 4] > 0
    full = ref.logits(tape, tokens, arch)
    np.testing.assert_allclose(
        gaps[1, 4], full[1, 4].max() - full[1, 4, tokens[1, 5]], rtol=1e-5)


# -- the readers, on made-up runs ---------------------------------------------

class _Reduced:
    def __init__(self, ops, t0, t1):
        self.ops, self.t0, self.t1 = [ops], t0, t1

    def seconds(self, pattern, device=0):
        return sum(d for n, _s, d in self.ops[device]
                   if pattern.search(n)) * 1e-9

    def busy_on(self, device=0):
        return sum(d for _n, _s, d in self.ops[device]) * 1e-9


def _span(name, start, dur):
    return types.SimpleNamespace(name=name, start=start, dur=dur,
                                 end=start + dur, heard=start + dur)


class _Spans:
    def __init__(self, spans):
        self.spans = spans

    def named(self, prefix, t0=None, t1=None):
        return [s for s in self.spans if s.name.startswith(prefix)
                and (t0 is None or s.end >= t0)
                and (t1 is None or s.end <= t1)]


def _run(cfg, reduced, spans=(), requests=()):
    return {"reduced": reduced, "peaks": PEAKS, "config": cfg,
            "slots": 64, "kind": "serve", "window": (10.0, 14.0),
            "spans": _Spans(list(spans)), "all_requests": list(requests)}


def test_the_state_updates_share_and_roofline_read_the_named_calls(
        manifest, cfg):
    least = arith.ssm_update_seconds(64, PEAKS, **cfg)["seconds"]
    call = "ssm_state_update.7 custom-call:tpu_custom_call"
    ops = [[call, 100.0 + i * 1e6, least * 1e9 / 0.8] for i in range(10)]
    ops += [["fusion.3 fusion", 50e6, 3 * sum(d for _n, _s, d in ops)],
            ["decode_attention.2 custom-call:tpu_custom_call", 90e6, 5e5]]
    run = _run(cfg, _Reduced(ops, 0.0, 1e9))
    roof = manifest.load_reader("ssm_update_roofline_pct.serve").read(run)
    assert roof == pytest.approx(80.0)
    share = manifest.load_reader(
        "ssm_update_time_share_pct.serve").read(run)
    busy = sum(d for _n, _s, d in ops)
    assert share == pytest.approx(
        100 * sum(d for n, _s, d in ops if n == call) / busy)
    # a call the window's edge clips is left out, not counted whole
    clipped = _run(cfg, _Reduced(ops + [[call, 1e9 - 10, 10.0]], 0.0, 1e9))
    assert manifest.load_reader("ssm_update_roofline_pct.serve").read(
        clipped) == pytest.approx(80.0)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_finds_nothing_where_there_is_nothing_to_read(
        manifest, cfg, name):
    read = manifest.load_reader(name).read
    assert read(_run(cfg, None)) is None                  # a rehearsal
    no_kernel = _Reduced([["fusion.1 fusion", 0.0, 5.0]], 0.0, 10.0)
    assert read(_run(cfg, no_kernel)) is None             # the parent
    other = dict(cfg)
    other.pop("arith")
    assert read(_run(other, no_kernel)) is None           # another config


def test_prefill_share_sums_the_spans_that_ended_in_the_window(manifest,
                                                              cfg):
    spans = [_span("generation::prefill[120]", 10.5, 0.2),
             _span("generation::prefill[9]", 12.0, 0.1),
             _span("generation::prefill[50]", 9.0, 0.3),      # before
             _span("generation::decode_step[1024]", 11.0, 0.02)]
    run = _run(cfg, _Reduced([], 0, 1), spans)
    assert manifest.load_reader("prefill_time_share_pct.serve").read(
        run) == pytest.approx(100 * 0.3 / 4.0)


def test_served_mfu_counts_prompts_and_the_tokens_decoded_in_the_window(
        manifest, cfg):
    spans = [_span("generation::prefill[120]", 10.5, 0.2)]
    inside = types.SimpleNamespace(first_token=11.0, completed=13.0,
                                   answer_len=101, prompt=[1] * 20)
    half = types.SimpleNamespace(first_token=13.0, completed=15.0,
                                 answer_len=41, prompt=[1] * 10)
    unfinished = types.SimpleNamespace(first_token=12.0, completed=None,
                                       answer_len=9, prompt=[1])
    run = _run(cfg, _Reduced([], 0, 1), spans, [inside, half, unfinished])
    want = arith.prefill_flops(120, **cfg) \
        + 100 * arith.decode_token_flops(20 + 1 + 50, **cfg) \
        + 20 * arith.decode_token_flops(10 + 1 + 10, **cfg)
    got = manifest.load_reader("step_mfu_pct.serve").read(run)
    assert got == pytest.approx(want / 4.0 / 197e12 * 100)
    assert 0 < got < 100


# -- the driver ---------------------------------------------------------------

def test_the_storage_check_names_an_array_of_the_wrong_width():
    from chipbench.drivers import serve_state
    table = {"weights": "bfloat16", "kv": "bfloat16", "conv": "bfloat16",
             "ssm": "float32", "scales": "float32"}

    def param(name, shape):
        return types.SimpleNamespace(name=name, shape=shape)

    arrays = {"w": ("bfloat16", [4, 4]), "scale": ("float32", [4]),
              "bias": ("bfloat16", [4]), "kv_cache.l0.k": ("bfloat16", 0),
              "ssm_state.l1": ("float32", 0)}

    def model(**over):
        have = dict(arrays, **over)
        lm = types.SimpleNamespace(main=types.SimpleNamespace(
            all_parameters=lambda: [param(n, have[n][1])
                                    for n in ("w", "scale", "bias")]))
        return types.SimpleNamespace(
            state_kinds={"kv": ["kv_cache.l0.k"], "ssm": ["ssm_state.l1"]},
            scope=types.SimpleNamespace(get=lambda n: types.SimpleNamespace(
                dtype=have[n][0])),
            programs={"prefill": {8: lm}},
            spec=types.SimpleNamespace(prompt_buckets=[8]))

    assert serve_state.storage_faults(model(), table) == []
    assert [f[0] for f in serve_state.storage_faults(
        model(**{"ssm_state.l1": ("bfloat16", 0)}), table)] == \
        ["ssm_state.l1"]
    assert [f[0] for f in serve_state.storage_faults(
        model(w=("float32", [4, 4])), table)] == ["w"]
    assert [f[0] for f in serve_state.storage_faults(
        model(scale=("float16", [4])), table)] == ["scale"]


def test_the_probe_reads_what_the_tokens_cannot(cfg, monkeypatch):
    """A state that stays what its prefill wrote is refused by the
    probe, whatever the tokens say: a model whose decode steps do not
    touch their slots."""
    import paddle_tpu as pt
    from chipbench.drivers import serve_state, sizes
    from paddle_tpu.serving.generation import GenerationModel
    pt.reset_default_programs()
    args, _ = sizes(cfg, {"traffic": {}}, rehearse=True)
    spec = serve_state.build_spec(cfg, args, 4, rehearse=True)
    model = GenerationModel.build(spec)
    lm = model.programs["prefill"][spec.prompt_buckets[0]]
    tape = [np.asarray(model.scope.get(p.name))
            for p in lm.main.all_parameters()]
    ctx = types.SimpleNamespace(config=cfg, seed=5, rehearse=True)

    def probe():
        return serve_state.probe_errors(
            ctx, spec, tape, serve_state.run_probes(ctx, model, spec))

    sound = probe()
    assert all(sound[k] <= serve_state.STATE_TOL[k] for k in sound), sound
    monkeypatch.setattr(model, "run_decode", lambda *a, **k: None)
    stale = probe()
    assert stale["ssm"] > 0.2 and stale["conv"] > 0.2 and stale["kv"] > 0.2


def test_a_state_rounded_every_step_is_refused_whatever_its_dtype(
        cfg, monkeypatch):
    """A recurrent state rounded to bfloat16 after every step and kept
    in its float32 arrays: the storage table passes it, the share of
    its values that hold nothing below 8 bits refuses it, and the slow
    heads' error (here every head: the toy stack has no slow one) reads
    the roundings beside the sound state's."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    from chipbench.drivers import serve_state, sizes
    from paddle_tpu.serving.generation import GenerationModel
    pt.reset_default_programs()
    args, _ = sizes(cfg, {"traffic": {}}, rehearse=True)
    spec = serve_state.build_spec(cfg, args, 4, rehearse=True)
    model = GenerationModel.build(spec)
    lm = model.programs["prefill"][spec.prompt_buckets[0]]
    tape = [np.asarray(model.scope.get(p.name))
            for p in lm.main.all_parameters()]
    ctx = types.SimpleNamespace(config=cfg, seed=5, rehearse=True)
    monkeypatch.setattr(serve_state, "SLOW_RATE", 10.0)

    def probe():
        probes = serve_state.run_probes(ctx, model, spec)
        return (serve_state.bf16_share(probes, model.state_kinds["ssm"]),
                serve_state.probe_errors(ctx, spec, tape, probes))

    share, sound = probe()
    assert share < serve_state.BF16_SHARE_TOL
    assert 0 < sound["ssm_slow"] <= sound["ssm"]   # pooled, not the worst

    step = model.run_decode

    def rounding(*a, **k):
        out = step(*a, **k)
        for name in model.state_kinds["ssm"]:
            model.scope.set(name, model.scope.get(name).astype(
                jnp.bfloat16).astype(jnp.float32))
        return out

    monkeypatch.setattr(model, "run_decode", rounding)
    share, rounded = probe()
    assert serve_state.storage_faults(model, cfg["storage_dtypes"]) == []
    assert share == 1.0 > serve_state.BF16_SHARE_TOL
    assert rounded["ssm_slow"] > sound["ssm_slow"]


def test_the_slow_heads_are_the_heads_under_the_rate(cfg, monkeypatch):
    """``ssm_slow`` reads the heads alone whose nominal forgetting a
    step is at most SLOW_RATE: a fault in a fast head moves ``ssm`` and
    not ``ssm_slow``; with no head under the rate a run that is no
    rehearsal reads inf."""
    from chipbench import reference_granite as ref
    from chipbench.drivers import serve_state
    arch = _toy_arch()
    tape, _, _ = _toy_weights()
    rates = ref.rates(tape, arch)
    assert sorted(rates) == [i for i, kind in enumerate(arch["layer_types"])
                             if kind == "mamba"]
    _, layers, _ = ref.layers_of(tape, arch["layer_types"])
    first = sorted(rates)[0]
    w = layers[first]
    np.testing.assert_allclose(
        rates[first], np.log1p(np.exp(w["dt_bias"])) * np.exp(w["a_log"]),
        rtol=1e-6)
    cut = float(np.median(rates[first]))
    slow = rates[first] <= cut
    assert 0 < slow.sum() < len(slow)
    tokens = np.random.default_rng(0).integers(1, 16, (2, 7))
    kept = ref.states(tape, tokens, arch)

    def held(fault_in=None):
        out = {}
        for i, k in enumerate(kept):
            if arch["layer_types"][i] == "mamba":
                final = np.array(np.moveaxis(np.asarray(k[0]), 3, 1))
                if fault_in is not None and i == first:
                    final[:, :, fault_in] *= 1.5
                out[f"ssm_state.l{i}"] = final.reshape(len(tokens),
                                                       final.shape[1], -1)
                out[f"conv_state.l{i}"] = np.asarray(k[1]).reshape(
                    len(tokens), -1)
            else:
                out[f"kv_cache.l{i}.k"], out[f"kv_cache.l{i}.v"] = k
        return out

    config = dict(cfg, reference=dict(
        states="chipbench.reference_granite.states",
        rates="chipbench.reference_granite.rates"))
    ctx = types.SimpleNamespace(config=config, seed=0, rehearse=False)
    spec = types.SimpleNamespace(arch={"arch": arch})
    monkeypatch.setattr(serve_state, "SLOW_RATE", cut)

    def errors(fault_in=None):
        return serve_state.probe_errors(ctx, spec, tape,
                                        [(tokens, held(fault_in))])

    assert errors() == {"ssm": 0, "conv": 0, "kv": 0, "ssm_slow": 0}
    fast = errors(int(np.argmax(rates[first])))
    assert fast["ssm"] > 0.01 and fast["ssm_slow"] == 0
    slowest = errors(int(np.argmin(rates[first])))
    assert slowest["ssm"] > 0.01 and slowest["ssm_slow"] > 0.01
    monkeypatch.setattr(serve_state, "SLOW_RATE", 0.0)
    assert errors()["ssm_slow"] == float("inf")


def test_bf16_share_counts_the_values_with_nothing_below_eight_bits():
    import jax.numpy as jnp
    from chipbench.drivers import serve_state
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 64, 8)),
                    jnp.float32)
    whole = x.astype(jnp.bfloat16).astype(jnp.float32)
    assert serve_state.bf16_share([(None, {"a": x})], ["a"]) < 0.001
    assert serve_state.bf16_share([(None, {"a": whole})], ["a"]) == 1.0
    half = jnp.concatenate([x, whole])
    assert serve_state.bf16_share([(None, {"a": half})], ["a"]) \
        == pytest.approx(0.5, abs=0.001)
    # zeros are no evidence either way; a state of nothing but zeros
    # (never written) is not a float32 state
    padded = jnp.concatenate([x, jnp.zeros_like(x)])
    assert serve_state.bf16_share([(None, {"a": padded})], ["a"]) < 0.001
    assert serve_state.bf16_share(
        [(None, {"a": jnp.zeros_like(x)})], ["a"]) == 1.0


def test_the_driver_imports_what_it_shares_with_serve():
    from chipbench.drivers import serve, serve_state
    for name in ("match_first_tokens", "_await", "percentile",
                 "CHECKED_REQUESTS"):
        assert getattr(serve_state, name) is getattr(serve, name)
    # the longest reply (1024 tokens of up to 34 ms) ends inside the wait
    assert serve_state.GRACE_S > 1024 * 0.034 > serve.GRACE_S
    assert serve_state.traffic is serve.traffic
    assert 0 < serve_state.GAP_MEAN_TOL < serve_state.GAP_MAX_TOL < 0.017


ENV = dict(os.environ, JAX_PLATFORMS="cpu")
ENV.pop("JAX_COMPILATION_CACHE_DIR", None)


@pytest.mark.parametrize("trace,expect", [
    (0, {"serve_tokens_per_s", "tpot_ms_p95", "setup_s"}),
    (1, {"decode_step_ms.serve", "kv_live_share_pct.serve",
         "first_step_other_s", "compile_backend_s"})])
def test_rehearsal_of_the_cell(trace, expect):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "1.5",
         "--trace", str(trace), "--rehearse"],
        cwd=REPO, env=ENV, capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    line = lines[-1]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    # off the chip no device-trace metric is reported from host numbers
    assert set(line["metrics"]) >= expect
    assert not set(line["metrics"]) & set(NEW_READERS)
    assert "ttft_ms_p95" not in line["metrics"]
    notes = next(ln for ln in lines if "check" in ln)
    assert notes["ttft_ms"]["p95"] > 0           # measured, and printed
    assert notes["check"]["storage_faults"] == []
    assert set(notes["state_reserved_bytes"]) == {"kv", "conv", "ssm"}
    assert notes["check"]["checked_tokens"] > 0
    # each kind of state, probed in slots of its own after the window,
    # against what the reference keeps of the same rows
    errors, limits = notes["check"]["state_error"], \
        notes["check"]["state_tol"]
    assert set(errors) == set(limits) == {"ssm", "conv", "kv", "ssm_slow"}
    assert all(0 < errors[k] <= limits[k] for k in ("ssm", "conv", "kv"))
    # a toy stack's eight heads hold no slow one: nothing to read
    assert errors["ssm_slow"] == 0
    assert notes["check"]["state_bf16_share"] \
        <= notes["check"]["bf16_share_tol"]
