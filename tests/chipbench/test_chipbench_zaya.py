"""The ``zaya1-8b`` configuration and its cell
``zaya1-8b.serve-reasoning``: the manifest's entries (found BY NAME,
wherever later PRs put theirs), the configuration file against the
published keys, the closed forms of chipbench/arith_zaya.py at the
published sizes, the plain reference on its own, the four readers on
made-up runs, the driver's storage check and controls, and the
rehearsal of the cell, sound and under every control."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import chipbench
from chipbench import arith_zaya as arith
from chipbench import reference_zaya as ref
from chipbench.manifest import Manifest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    chipbench.__file__)))
CELL = "zaya1-8b.serve-reasoning"
CONFIG = "zaya1-8b"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_READERS = ("moe_expert_time_share_pct.serve",
               "moe_expert_roofline_pct.serve", "moe_live_rows.serve",
               "decode_attention_time_share_pct.serve")


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
    assert cell["traffic"] == "serve-reasoning"
    entry = manifest.config_entry(CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == "chipbench/configs/zaya1-8b.json"
    assert entry["source"] == \
        "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"
    # the driver's rule, which ``problems()`` holds a cell's ``why`` to
    # and not a configuration's: 1 to 200 printable characters, one line
    for why in (entry["why"], cell["why"]):
        assert 1 <= len(why) <= 200 and why.isascii() and why.isprintable()


@pytest.mark.parametrize("name", [
    "serve_tokens_per_s", "tpot_ms_p95", "decode_step_ms.serve",
    "kv_live_share_pct.serve", "engine_host_ms.serve",
    "prefill_time_share_pct.serve", "step_mfu_pct.serve", *NEW_READERS])
def test_the_cell_is_on_the_lists_it_reports(manifest, name):
    assert CELL in _metric(manifest, name)["workloads"]


@pytest.mark.parametrize("name", [
    "ttft_ms_p95", "queue_wait_ms_p95.serve", "engine_queue_ms_p95.serve",
    "first_token_ms_p95.serve"])
def test_time_to_first_token_is_not_among_the_cells_metrics(manifest,
                                                            name):
    assert CELL not in _metric(manifest, name)["workloads"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_lists_this_cell_alone_and_moves_tpot(manifest, name):
    m = _metric(manifest, name)
    assert m["workloads"] == [CELL] and m["moves"] == "tpot_ms_p95"
    assert m in manifest.metrics_for(CELL, "per_layer")
    assert manifest.load_reader(name).__doc__    # says what it reads


def test_the_cells_file_gives_the_issues_traffic(manifest):
    w = manifest.load_workload(CELL)
    t = w["traffic"]
    assert w["kind"] == "serve_experts" and t["slots"] == 96
    assert t["queue_capacity"] == 1024 and t["ramp_s"] == 20.0
    assert t["prompt_len"] == {"median": 80, "sigma": 1.0, "min": 8,
                               "max": 768}
    assert t["answer_len"] == {"median": 400, "sigma": 0.8, "min": 32,
                               "max": 1280}
    others = [manifest.load_workload(c)["traffic"]["base_seed"]
              for c in ("decoder-lm-base.serve-chat",
                        "granite-4p0-h-micro.serve-sessions")]
    assert t["base_seed"] not in others
    # under what the slots can turn over at the bytes' floor of a step
    assert 0 < t["rate_per_s"] < 96 / (0.0125 * 530)
    assert t["prompt_len"]["max"] + t["answer_len"]["max"] == 2048


# -- the configuration ------------------------------------------------------

PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2,
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "lm_head_bias": False, "max_position_embeddings": 131072,
    "model_type": "zaya", "moe_intermediate_size": 2048,
    "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-05, "router_hidden_size": 256,
    "sliding_window": None, "tie_word_embeddings": True,
    "vocab_size": 262272,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5,
                           "rope_theta": 10000, "rope_type": "default"},
        "rope_type": "default"}}


def test_the_files_keep_every_published_width_and_list_the_cut(cfg):
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    # the one cut: 20 of the 40 layers, every one of the published kind
    assert cfg["num_hidden_layers"] == 20
    assert cfg["layer_types"] == ["hybrid"] * 20
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert "two pipeline stages" in cfg["reduced_why"]
    assert cfg["builder"]["args"]["vocab_size"] == cfg["vocab_size"]
    assert cfg["builder"]["family"] == "cca_moe"
    for key in ("shifted_value_head", "norm_then_rotation",
                "norm_constant_and_tau", "convolution_biases", "gamma",
                "softmax_before_bias", "weight_not_renormalised",
                "initialisers", "embedding_std", "decoding",
                "left_out_residual_scaling", "left_out_skip_choice"):
        assert cfg["assumed"][key]
    for key in ("left_out_residual_scaling", "left_out_skip_choice"):
        assert cfg["assumed"][key].startswith("DEPARTURE")
    assert cfg["storage_dtypes"] == {
        "weights": "bfloat16", "kv": "bfloat16", "conv": "bfloat16",
        "scales": "float32"}


def test_the_driver_builds_the_spec_from_the_published_keys(cfg):
    from chipbench.drivers import serve_experts, sizes
    from paddle_tpu.models.cca_moe import ARCH_KEYS
    args, _ = sizes(cfg, {"traffic": {}}, rehearse=False)
    spec = serve_experts.build_spec(cfg, args, 96, rehearse=False)
    assert spec.family == "cca_moe" and spec.slots == 96
    assert spec.arch["arch"] == {k: cfg[k] for k in ARCH_KEYS}
    assert spec.arch["dtypes"] == cfg["storage_dtypes"]
    assert spec.prompt_buckets == [128, 256, 1024]
    assert spec.cache_buckets == [1024, 2048] and spec.eos_id == -1
    assert spec.max_seq_len == 2048 and spec.vocab_size == 262272
    toy = serve_experts.build_spec(
        cfg, sizes(cfg, {"traffic": {}}, rehearse=True)[0], 4, True)
    assert toy.arch["arch"]["hidden_size"] == 64
    assert toy.arch["arch"]["cca_time1"] == 2


# -- the closed forms, at the published sizes ---------------------------------

def test_parameters_by_part_and_in_all(cfg):
    assert arith.attention_matrix_params(**cfg) == 5_570_560
    assert arith.router_params(**cfg) == 659_985
    assert arith.router_params(True, **cfg) == 659_984
    assert 16 * arith.expert_params(**cfg) == 201_326_592
    assert arith.layer_params(**cfg) == 207_566_355
    assert arith.model_params(**cfg) == 4_688_462_203
    whole = dict(cfg, layer_types=["hybrid"] * 40)
    assert arith.model_params(**whole) // 10 ** 6 == 8839    # "8.4B-A0.76B"
    active = arith.model_params(**whole) \
        - 40 * 15 * arith.expert_params(**cfg)
    assert 0.7e9 < active - 262272 * 2048 < 0.8e9


def test_stored_bytes_of_weights_and_state(cfg):
    assert arith.weight_bytes(**cfg) == 9_403_491_820
    reserved = arith.state_bytes(96, 2048, **cfg)
    assert reserved == {"kv": 4_026_531_840, "conv": 10_321_920}
    # a position a layer: 256 key and 256 value columns of 2 bytes
    assert reserved["kv"] == 96 * 2048 * 20 * 1024
    total = arith.weight_bytes(**cfg) + sum(reserved.values())
    assert 13.4e9 < total < 13.5e9            # over 12.5 GB, under 16


def test_a_decode_steps_bytes_are_mostly_experts(cfg):
    moved = arith.decode_step_bytes(16 * 20, 96 * 450, **cfg)
    assert moved["experts"] == 8_053_063_680
    assert moved["head"] == 1_074_266_112
    assert moved["kv_live"] == 96 * 450 * 20 * 1024
    assert 0.75 < moved["experts"] / moved["total"] < 0.8
    # an expert no pick reaches is not read
    fewer = arith.decode_step_bytes(10 * 20, 96 * 450, **cfg)
    assert fewer["experts"] * 16 == moved["experts"] * 10
    assert 0.0120 < moved["total"] / PEAKS["hbm_bytes_per_s"] < 0.0130


def test_the_grouped_products_are_bound_by_their_bytes(cfg):
    least = arith.experts_seconds(320, 96 * 20, PEAKS, **cfg)
    assert least["bound"] == "bandwidth"
    assert least["seconds"] == pytest.approx(
        (8_053_063_680 + 1920 * 6 * 2048 * 2) / 819e9)
    attn = arith.decode_attention_seconds(96, 96 * 2048, PEAKS, **cfg)
    assert attn["bound"] == "bandwidth"
    assert attn["seconds"] == pytest.approx(
        (96 * 2048 * 1024 + 96 * 2 * 1024 * 2) / 819e9)


def test_model_flops_of_a_token_and_of_a_prompt(cfg):
    one = arith.decode_token_flops(450, **cfg)
    stack = 20 * 2 * (5_570_560 + 2 * 1280 + 2048 * 256 + 2 * 256 * 256
                      + 256 * 16 + 3 * 2048 * 2048)
    assert one == stack + 20 * 8 * 4 * 128 * 450 + 2 * 2048 * 262272
    assert arith.prefill_flops(125, **cfg) == 125 * stack \
        + 20 * 8 * 4 * 128 * (125 * 126 // 2) + 2 * 2048 * 262272
    # the head is over half of a decoded token's products: 20 layers of
    # ONE expert each beside 262,272 rows
    assert 0.5 < 2 * 2048 * 262272 / one < 0.6


# -- the reference, on its own ------------------------------------------------

ARCH = dict(hidden_size=32, head_dim=8, num_attention_heads=4,
            num_key_value_heads=2, cca_time0=2, cca_time1=2,
            layer_types=["hybrid"] * 2,
            rope_parameters={"hybrid": {"partial_rotary_factor": 0.5,
                                        "rope_theta": 5000000,
                                        "rope_type": "default"}},
            num_experts=3, num_experts_per_tok=1, moe_intermediate_size=16,
            router_hidden_size=8, rms_norm_eps=1e-5)
VOCAB = 20


def _tape(seed=0):
    rng = np.random.default_rng(seed)
    d, dh, h, c = 32, 8, 4, 2
    lat, f, rh, e = (h + c) * dh, 16, 8, 3

    def m(*shape, scale=0.3):
        return rng.normal(0, scale, shape).astype(np.float32)

    tape = [m(VOCAB, d, scale=1.0)]
    for i in range(2):
        tape += [np.ones(d, np.float32), m(2, lat), m(lat), m(2 * lat, dh),
                 m(lat), np.asarray([1.0, 0.8], np.float32), m(d, h * dh),
                 m(d, c * dh), m(d, dh), m(d, dh), m(h * dh, d),
                 np.ones(d, np.float32), m(d, rh), m(rh, rh), m(rh),
                 m(rh, rh), m(rh), m(rh, e, scale=2.0)]
        if i:
            tape.append(np.asarray([0.5], np.float32))
        tape += [np.zeros(e, np.float32), m(e * d, f), m(e * d, f),
                 m(e * f, d)]
    return tape + [np.ones(d, np.float32)]


def test_the_reference_reads_its_tape_by_layer_and_counts_it():
    tape = _tape()
    table, layers, final = ref.layers_of(tape, 2)
    assert table.shape == (VOCAB, 32) and final.shape == (32,)
    assert "gamma" not in layers[0] and layers[1]["gamma"].shape == (1,)
    assert layers[1]["c1"].shape == (2 * 48, 8)
    with pytest.raises(ValueError, match="tape holds"):
        ref.layers_of(tape[:-1], 2)


def test_the_reference_is_causal_and_a_prefix_is_the_full_pass():
    tape = _tape(1)
    seq = np.random.default_rng(1).integers(0, VOCAB, (2, 12))
    whole = ref.logits(tape, seq, ARCH)
    np.testing.assert_allclose(ref.logits(tape, seq[:, :7], ARCH),
                               whole[:, :7], rtol=1e-4, atol=1e-5)
    other = seq.copy()
    other[:, 9] = (other[:, 9] + 1) % VOCAB
    np.testing.assert_array_equal(ref.logits(tape, other, ARCH)[:, :9],
                                  whole[:, :9])


def test_the_shifted_value_the_convolutions_and_the_pick_by_hand():
    """One layer's pieces against loops over positions: the second
    value head is the token before's, the depthwise and the grouped
    convolution read one row back, and the pick is the arg max of the
    softmax of an MLP of the carried r."""
    import jax
    import jax.numpy as jnp
    tape = _tape(2)
    _, layers, _ = ref.layers_of(tape, 2)
    w = {k: jnp.asarray(v) for k, v in layers[1].items()}
    static = dict(ref._static(ARCH))
    u = jnp.asarray(np.random.default_rng(3).normal(0, 1, (1, 6, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, (keys, values, z, a, v2) = ref.cca(u, w, static)
        r_prev = jnp.ones((1, 6, 8))
        _, r, pick, margin, _ = ref.moe(u, w, r_prev, static)
    un, vn = np.asarray(u)[0], np.asarray(values)[0]      # [c, S, dh]
    for t in range(6):
        np.testing.assert_allclose(vn[0, t], un[t] @ layers[1]["w_v1"],
                                   rtol=1e-4, atol=1e-5)
        want = un[t - 1] @ layers[1]["w_v2"] if t else np.zeros(8)
        np.testing.assert_allclose(vn[1, t], want, rtol=1e-4, atol=1e-5)
        zt = np.asarray(z)[0]
        before = zt[t - 1] if t else np.zeros(48)
        np.testing.assert_allclose(
            np.asarray(a)[0, t], layers[1]["c0"][0] * before
            + layers[1]["c0"][1] * zt[t] + layers[1]["b0"], rtol=1e-4,
            atol=1e-5)
        rt = un[t] @ layers[1]["w_d"] + 0.5 * 1.0
        np.testing.assert_allclose(np.asarray(r)[0, t], rt, rtol=1e-4)
    # every key head has length tau sqrt(d_h): the rotation keeps it
    lengths = np.linalg.norm(np.asarray(keys)[0], axis=-1)
    np.testing.assert_allclose(lengths[0], np.sqrt(8) * 1.0, rtol=1e-4)
    np.testing.assert_allclose(lengths[1], np.sqrt(8) * 0.8, rtol=1e-4)
    assert pick.shape == (1, 6) and (np.asarray(margin) >= 0).all()


def test_choice_gaps_are_zero_on_the_references_own_greedy_tokens():
    tape = _tape(4)
    seq = [3]
    for _ in range(7):
        seq.append(int(np.argmax(ref.logits(tape, np.asarray([seq]),
                                            ARCH)[0, -1])))
    tokens = np.asarray([seq, seq])
    tokens[1, 5] = (tokens[1, 5] + 1) % VOCAB           # one other choice
    out = ref.choice_gaps(tape, [tokens], ARCH)[0]
    assert out.shape == (2, 8, 2)
    np.testing.assert_array_equal(out[0, :7, 0], 0.0)
    assert out[1, 4, 0] > 0
    full = ref.logits(tape, tokens, ARCH)
    np.testing.assert_allclose(
        out[1, 4, 0], full[1, 4].max() - full[1, 4, tokens[1, 5]],
        rtol=1e-4)
    # the second number is the position's smallest margin over the layers
    kept = ref.states(tape, tokens, ARCH)
    np.testing.assert_allclose(
        out[..., 1], np.min([k["margin"] for k in kept], axis=0), rtol=1e-6)


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


def _run(cfg, reduced, spans=(), seen=None):
    return {"reduced": reduced, "peaks": PEAKS, "config": cfg,
            "slots": 96, "kind": "serve", "window": (10.0, 14.0),
            "spans": _Spans(list(spans)), "all_requests": [],
            "experts_in_window": seen, "expert_layers": 20,
            "cache_buckets": [1024, 2048]}


def test_live_rows_are_the_routed_rows_a_layer_a_step(manifest, cfg):
    read = manifest.load_reader("moe_live_rows.serve").read
    seen = dict(rows=90 * 20 * 130, experts_read=16 * 20 * 130, steps=130)
    assert read(_run(cfg, None, seen=seen)) == pytest.approx(90.0)
    assert read(_run(cfg, None, seen=None)) is None          # the parent
    assert read(_run(cfg, None, seen=dict(seen, steps=0))) is None
    assert read(dict(_run(cfg, None, seen=seen), kind="train")) is None


def test_the_experts_share_and_roofline_read_the_grouped_calls(manifest,
                                                               cfg):
    seen = dict(rows=90 * 20 * 2, experts_read=16 * 20 * 2, steps=2)
    least = arith.experts_seconds(seen["experts_read"], seen["rows"],
                                  PEAKS, **cfg)["seconds"]
    # the window (10 s .. 14 s on the host) is 0 .. 4e9 ns of the trace;
    # two decode steps and a prefill between them
    spans = [_span("generation::decode_step[2048]", 10.5, 0.03),
             _span("generation::prefill[100]", 10.6, 0.05),
             _span("generation::decode_step[2048]", 10.7, 0.03)]
    call = "ragged-dot-none.3 custom-call:tpu_custom_call"
    each = least * 1e9 / 0.4 / 4
    ops = [[call, 0.5e9 + i * 1e6, each] for i in range(2)] \
        + [[call, 0.7e9 + i * 1e6, each] for i in range(2)] \
        + [[call, 0.62e9, 7 * each],                  # the prefill's
           ["sort.1 sort", 0.51e9, each],
           ["fusion.9 fusion", 0.71e9, 10 * each]]
    run = _run(cfg, _Reduced(ops, 0.0, 4e9), spans, seen)
    roof = manifest.load_reader("moe_expert_roofline_pct.serve").read(run)
    assert roof == pytest.approx(40.0)
    share = manifest.load_reader(
        "moe_expert_time_share_pct.serve").read(run)
    assert share == pytest.approx(100 * 12 / 22)


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


def test_decode_attention_share_reads_the_op_table(manifest, cfg,
                                                   monkeypatch):
    from chipbench import program_ops
    ref_of = types.SimpleNamespace
    table = types.SimpleNamespace(ops={
        "fusion.1": ref_of(op_type="scaled_dot_product_attention"),
        "fusion.2": ref_of(op_type="mul"),
        "ragged-dot-none.3": ref_of(op_type="moe_experts")})
    monkeypatch.setattr(program_ops, "tables", lambda: iter([table]))
    spans = [_span("generation::decode_step[2048]", 10.5, 0.03),
             _span("generation::decode_step[1024]", 10.6, 0.01)]
    ops = [["fusion.1 fusion", 0.505e9, 2e6],
           ["fusion.2 fusion", 0.510e9, 5e6],
           ["ragged-dot-none.3 custom-call:tpu_custom_call", 0.52e9, 3e6],
           ["fusion.1 fusion", 0.605e9, 9e6]]      # the other bucket's
    run = _run(cfg, _Reduced(ops, 0.0, 4e9), spans)
    read = manifest.load_reader(
        "decode_attention_time_share_pct.serve").read
    assert read(run) == pytest.approx(20.0)


# -- the driver's own checks --------------------------------------------------

def test_the_storage_check_names_an_array_of_the_wrong_width():
    from chipbench.drivers import serve_experts
    table = {"weights": "bfloat16", "kv": "bfloat16", "conv": "bfloat16",
             "scales": "float32"}

    def model(**wrong):
        arrays = {"kv_cache.l0.k": ("bfloat16", 0),
                  "conv_state.l0.z": ("bfloat16", 0),
                  "fc_0.w_0": ("bfloat16", [4, 4]),
                  "cca_0.w_1": ("bfloat16", [4]),
                  "norm_0.w_0": ("float32", [4]),
                  "router_0.w_0": ("float32", [4, 4]),
                  "router_0.w_1": ("float32", [4])}
        arrays.update(wrong)
        params = [types.SimpleNamespace(name=n, shape=s)
                  for n, (_d, s) in arrays.items() if s]
        lm = types.SimpleNamespace(main=types.SimpleNamespace(
            all_parameters=lambda: params))
        return types.SimpleNamespace(
            state_kinds={"kv": ["kv_cache.l0.k"],
                         "conv": ["conv_state.l0.z"]},
            scope=types.SimpleNamespace(get=lambda n: types.SimpleNamespace(
                dtype=arrays[n][0])),
            programs={"prefill": {8: lm}},
            spec=types.SimpleNamespace(prompt_buckets=[8]))

    assert serve_experts.storage_faults(model(), table) == []
    for name, (dtype, shape) in {
            "kv_cache.l0.k": ("float32", 0),
            "fc_0.w_0": ("float32", [4, 4]),
            "router_0.w_0": ("bfloat16", [4, 4]),
            "norm_0.w_0": ("float16", [4])}.items():
        faults = serve_experts.storage_faults(
            model(**{name: (dtype, shape)}), table)
        assert [f[0] for f in faults] == [name]


def test_the_driver_imports_what_it_shares_and_places_its_limits():
    from chipbench.drivers import serve, serve_experts, serve_state
    for name in ("match_first_tokens", "_await", "percentile"):
        assert getattr(serve_experts, name) is getattr(serve, name)
    for name in ("GRACE_S", "run_probes", "_check_against_reference"):
        assert getattr(serve_experts, name) is getattr(serve_state, name)
    assert serve_experts.traffic is serve.traffic
    # the longest reply (1280 tokens of ~30 ms) ends inside the wait
    assert serve_experts.GRACE_S > 1280 * 0.030
    assert 0 < serve_experts.GAP_MEAN_TOL < serve_experts.GAP_MAX_TOL
    assert 0 < serve_experts.MARGIN_MIN < 1 / 16
    assert len(serve_experts.CONTROLS) == 7


# -- the rehearsal of the cell -------------------------------------------------

ENV = dict(os.environ, JAX_PLATFORMS="cpu")
ENV.pop("JAX_COMPILATION_CACHE_DIR", None)


def _rehearse(trace, *extra):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "1.5",
         "--trace", str(trace), "--rehearse", *extra],
        cwd=REPO, env=ENV, capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    return lines[-1], next(ln for ln in lines if "check" in ln)


@pytest.mark.parametrize("trace,expect", [
    (0, {"serve_tokens_per_s", "tpot_ms_p95", "setup_s"}),
    (1, {"decode_step_ms.serve", "kv_live_share_pct.serve",
         "moe_live_rows.serve", "first_step_other_s",
         "compile_backend_s"})])
def test_rehearsal_of_the_cell(trace, expect):
    line, notes = _rehearse(trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert set(line["metrics"]) >= expect
    # off the chip no device-trace metric is reported from host numbers
    assert not set(line["metrics"]) & (set(NEW_READERS)
                                       - {"moe_live_rows.serve"})
    assert "ttft_ms_p95" not in line["metrics"]
    if trace:
        # the live slots' rows: more than none, never the 4 slots' worth
        assert 0 < line["metrics"]["moe_live_rows.serve"]["value"] < 4
    check = notes["check"]
    assert notes["ttft_ms"]["p95"] > 0           # measured, and printed
    assert check["storage_faults"] == [] and check["control"] is None
    assert set(notes["state_reserved_bytes"]) == {"kv", "conv"}
    assert check["judged_tokens"] > 0
    assert check["set_aside_share"] <= check["set_aside_max"]
    errors, limits = check["state_error"], check["state_tol"]
    assert all(0 < errors[k] <= limits[k] for k in limits)
    assert errors["picks"] <= check["pick_mismatch_max"]
    assert check["experts_low_bits_share"] <= check["low_bits_share_max"]
    seen = notes["experts"]["in_window"]
    assert 0 < seen["experts_read"] <= seen["rows"]


def _controls():
    from chipbench.drivers import serve_experts
    return serve_experts.CONTROLS


@pytest.mark.parametrize("control", _controls())
def test_each_control_fails_the_rehearsals_comparison(control):
    line, notes = _rehearse(0, "--set", f'control="{control}"')
    assert notes["check"]["control"] == control
    assert line["correct"] is False and line["failed"] == 0
    # by a limit of the comparison, not by a crash or a lost request
    check = notes["check"]
    over = [check["reference_gap_max"] > check["gap_max_tol"],
            check["reference_gap_mean"] > check["gap_mean_tol"],
            check["state_error"]["kv"] > check["state_tol"]["kv"],
            check["state_error"]["conv"] > check["state_tol"]["conv"],
            check["state_error"]["picks"] > check["pick_mismatch_max"],
            check["experts_low_bits_share"] > check["low_bits_share_max"]]
    assert any(over)
    assert check["storage_faults"] == []
    assert check["wrong_token_counts"] == 0
