"""The ``olmo-hybrid-7b`` configuration and its cell
``olmo-hybrid-7b.serve-reasoning``: the manifest's entries (found BY
NAME, wherever later PRs put theirs), the configuration file against
the published keys and its cut, the closed forms of
chipbench/arith_olmo_hybrid.py at the published sizes, the plain
reference against the delta rule by hand, the two readers on made-up
runs, the driver's probe against its six deliberate faults at a toy
size, and the rehearsal of the cell."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import chipbench
from chipbench import arith_olmo_hybrid as arith
from chipbench import reference_olmo_hybrid as ref
from chipbench.manifest import Manifest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    chipbench.__file__)))
CELL = "olmo-hybrid-7b.serve-reasoning"
CONFIG = "olmo-hybrid-7b"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_READERS = ("delta_update_time_share_pct.serve",
               "delta_update_roofline_pct.serve")


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
    assert cell["why"] == manifest.load_workload(CELL)["why"]
    entry = manifest.config_entry(CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == "chipbench/configs/olmo-hybrid-7b.json"
    assert entry["source"] == ("https://huggingface.co/allenai/"
                               "Olmo-Hybrid-7B/blob/main/config.json")
    # one configuration, one cell: no second in which the state does
    # little
    assert [w["name"] for w in manifest.data["workloads"]
            if w["config"] == CONFIG] == [CELL]


@pytest.mark.parametrize("group,name", [("configs", CONFIG),
                                        ("workloads", CELL)])
def test_a_line_of_the_new_entries_fits_the_drivers_rule(manifest, group,
                                                        name):
    """The driver holds a configuration's `why` and `source` to 200
    printable characters on one line, as it does a cell's `why`;
    `problems()` checks the cells alone (the first check of this PR was
    refused on a 213-character `why`)."""
    (entry,) = [e for e in manifest.data[group] if e["name"] == name]
    for key in ("why", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200, (key, len(entry[key]))
            assert entry[key].isprintable() and entry[key].isascii()


@pytest.mark.parametrize("name", [
    "serve_tokens_per_s", "tpot_ms_p95", "decode_step_ms.serve",
    "kv_live_share_pct.serve", "engine_host_ms.serve",
    "engine_collect_ms.serve", "engine_idle_wait_share_pct.serve",
    "engine_cpu_ms.serve", "engine_off_cpu_ms.serve",
    "engine_stall_ms_max.serve", "prefill_time_share_pct.serve",
    "step_mfu_pct.serve", "decode_attention_time_share_pct.serve",
    *NEW_READERS])
def test_the_cell_is_on_the_lists_it_reports(manifest, name):
    assert CELL in _metric(manifest, name)["workloads"]


@pytest.mark.parametrize("name", [
    "ttft_ms_p95", "queue_wait_ms_p95.serve", "engine_queue_ms_p95.serve",
    "first_token_ms_p95.serve", "ssm_update_roofline_pct.serve",
    "moe_expert_roofline_pct.serve"])
def test_what_the_cell_does_not_report(manifest, name):
    """Time to first token is a queue's while prefill is serial inside
    admission; the other families' kernels are not in this program."""
    assert CELL not in _metric(manifest, name)["workloads"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_lists_this_cell_alone_and_moves_tpot(manifest, name):
    m = _metric(manifest, name)
    assert m["workloads"] == [CELL] and m["moves"] == "tpot_ms_p95"
    assert m["unit"] == "%" and m["layer"] == "Kernels"
    assert m["source"] == "device_trace"
    assert m in manifest.metrics_for(CELL, "per_layer")
    assert manifest.load_reader(name).__doc__    # says what it reads


def test_the_cells_file_gives_the_issues_traffic(manifest):
    w = manifest.load_workload(CELL)
    t = w["traffic"]
    assert w["kind"] == "serve_delta" and t["slots"] == 40
    assert t["queue_capacity"] == 1024 and t["ramp_s"] == 15.0
    # zaya1-8b.serve-reasoning's two distributions, letter for letter
    other = manifest.load_workload("zaya1-8b.serve-reasoning")["traffic"]
    assert t["prompt_len"] == other["prompt_len"] == {
        "median": 80, "sigma": 1.0, "min": 8, "max": 768}
    assert t["answer_len"] == other["answer_len"] == {
        "median": 400, "sigma": 0.8, "min": 32, "max": 1280}
    seeds = [manifest.load_workload(c["name"])["traffic"].get("base_seed")
             for c in manifest.data["workloads"] if c["name"] != CELL]
    assert t["base_seed"] not in seeds
    assert 0 < t["rate_per_s"] < 40 / (0.015 * 530)   # under the slots' cap
    assert t["rate_why"] and "sweep" in t["rate_why"]
    assert len(w["why"]) <= 200


# -- the configuration ------------------------------------------------------

PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 3840,
    "intermediate_size": 11008, "linear_allow_neg_eigval": True,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 96,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_value_head_dim": 192, "max_position_embeddings": 65536,
    "model_type": "olmo_hybrid", "num_attention_heads": 30,
    "num_key_value_heads": 30, "rms_norm_eps": 1e-06,
    "rope_parameters": {"rope_theta": None},
    "tie_word_embeddings": False, "vocab_size": 100352}


def test_the_configuration_holds_every_published_key_and_lists_its_cut(cfg):
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    # four whole periods of the published 3:1 pattern
    assert cfg["layer_types"] == (["linear_attention"] * 3
                                  + ["full_attention"]) * 4
    assert cfg["num_hidden_layers"] == 16 == len(cfg["layer_types"])
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"]["num_hidden_layers"] == 32
    assert "two pipeline stages" in cfg["reduced_why"]
    assert "two pipeline stages" in cfg["deployment"]
    assert cfg["builder"]["args"]["vocab_size"] == cfg["vocab_size"]
    for key in ("block", "positions", "qk_norm", "linear_layer", "chunk",
                "state_dtype", "state_layout", "initialisers",
                "embedding_std", "decoding"):
        assert cfg["assumed"][key], key
    assert cfg["storage_dtypes"] == {
        "weights": "bfloat16", "kv": "bfloat16", "conv": "bfloat16",
        "delta": "float32", "scales": "float32"}


def test_the_catalogs_every_number_is_in_the_file(cfg):
    """Where the model-configs guide's catalog is beside the tests (the
    builder's sandbox), every top-level number of its entry stands in
    the file under the same key, but for the listed cut."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    assert row["source_url"] == cfg["source"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (value, cfg[key]) == (32, 16)
        elif key == "layer_types":
            assert cfg[key] == value[:16] and value[16:] == value[:16]
        else:
            assert cfg[key] == value, key


def test_the_driver_builds_the_spec_from_the_published_keys(cfg):
    from chipbench.drivers import serve_delta, sizes
    from paddle_tpu.models.delta_hybrid import ARCH_KEYS
    args, _ = sizes(cfg, {"traffic": {}}, rehearse=False)
    spec = serve_delta.build_spec(cfg, args, 40, rehearse=False)
    assert spec.family == "delta_hybrid" and spec.slots == 40
    assert spec.arch["arch"] == {k: cfg[k] for k in ARCH_KEYS}
    assert spec.arch["dtypes"] == cfg["storage_dtypes"]
    assert spec.arch["embedding_std"] == 4.0
    assert spec.prompt_buckets == [128, 256, 1024]
    assert spec.cache_buckets == [1024, 2048] and spec.eos_id == -1
    assert spec.max_seq_len == 2048 and spec.vocab_size == 100352
    toy = serve_delta.build_spec(
        cfg, sizes(cfg, {"traffic": {}}, rehearse=True)[0], 4, True)
    assert toy.arch["arch"]["hidden_size"] == 64
    assert toy.arch["arch"]["linear_conv_kernel_dim"] == 4


# -- the closed forms, at the published sizes ---------------------------------

def test_parameters_by_layer_and_in_all(cfg):
    d = 3840
    # q and k 11.06 M each, v, the gate and the output 22.12 M each, a
    # and b 0.23 M, the MLP 126.81 M
    assert d * 2880 == 11_059_200 and d * 5760 == 22_118_400
    assert 2 * d * 30 == 230_400 and 3 * d * 11008 == 126_812_160
    assert arith.layer_params("linear_attention", **cfg) == 215_570_172
    assert arith.layer_params("full_attention", **cfg) == 185_809_920
    period = 3 * 215_570_172 + 185_809_920
    assert period == 832_520_436
    assert arith.model_params(**cfg) == 4 * period + 2 * 385_351_680 + d
    assert arith.model_params(**cfg) == 4_100_788_944


def test_stored_bytes_of_weights_and_state(cfg):
    assert round(arith.weight_bytes(**cfg) / 1e9, 2) == 8.20
    state = arith.state_bytes(40, 2048, **cfg)
    assert state["delta"] == 40 * 12 * 30 * 96 * 192 * 4
    assert round(state["delta"] / 1e9, 2) == 1.06
    assert round(state["conv"] / 1e9, 2) == 0.03
    assert round(state["kv"] / 1e9, 2) == 5.03
    # 61,440 bytes a cached position, 26.54 MB of matrix state a session
    assert arith.state_bytes(1, 1, **cfg)["kv"] == 61_440
    assert round(arith.state_bytes(1, 1, **cfg)["delta"] / 1e6, 2) == 26.54
    total = arith.weight_bytes(**cfg) + sum(state.values())
    assert round(total / 1e9, 1) == 14.3


def test_a_decode_steps_bytes_by_kind(cfg):
    step = arith.decode_step_bytes(40, 40 * 600, **cfg)
    assert step["delta"] == 2 * arith.state_bytes(40, 1, **cfg)["delta"]
    assert round(step["weights"] / 1e9, 2) == 7.43    # less the table
    assert round(step["kv_live"] / 1e9, 2) == 1.47
    assert round(step["delta"] / 1e9, 2) == 2.12
    assert 13.0 < step["total"] / PEAKS["hbm_bytes_per_s"] * 1e3 < 14.0


def test_one_state_update_is_bound_by_its_bytes(cfg):
    cost = arith.delta_update_cost(40, **cfg)
    state = 40 * 96 * 5760
    assert cost["flops"] == 7 * state
    assert cost["bytes"] == 4 * (2 * state + 5 * 40 * 5760
                                 + 2 * 40 * 2880)
    least = arith.delta_update_seconds(40, PEAKS, **cfg)
    assert least["bound"] == "bandwidth"
    assert 0.216e-3 < least["seconds"] < 0.226e-3


def test_model_flops_of_a_token_and_of_a_prompt(cfg):
    matrices = 12 * 215_516_160 + 4 * 185_794_560
    rule = 12 * (7 * 96 * 5760 + 2 * 4 * 11520)
    token = arith.decode_token_flops(0, **cfg)
    assert token == 2 * matrices + rule + 2 * 3840 * 100352
    # the recurrence is a small share of a layer's FLOPs, at any length
    assert rule / 12 / (2 * 215_516_160) < 0.01
    assert arith.decode_token_flops(1000, **cfg) - token == \
        4 * 30 * 4 * 128 * 1000
    n = 125
    assert arith.prefill_flops(n, **cfg) == \
        n * (2 * matrices + rule) + 4 * 30 * 4 * 128 * n * (n + 1) // 2 \
        + 2 * 3840 * 100352


# -- the reference ----------------------------------------------------------

def _toy_arch(**kw):
    return dict(dict(
        linear_num_value_heads=2, linear_key_head_dim=4,
        linear_value_head_dim=8, linear_conv_kernel_dim=4,
        linear_allow_neg_eigval=True, num_attention_heads=4,
        num_key_value_heads=4, rms_norm_eps=1e-6,
        layer_types=["linear_attention", "full_attention"]), **kw)


def _toy_weights(d=16, seed=0):
    rng = np.random.default_rng(seed)

    def mat(*shape):
        return rng.normal(0, 0.3, shape).astype(np.float32)

    def ones(n):
        return np.ones(n, np.float32)

    linear = dict(w_q=mat(d, 8), taps_q=mat(4, 8), w_k=mat(d, 8),
                  taps_k=mat(4, 8), w_v=mat(d, 16), taps_v=mat(4, 16),
                  w_a=mat(d, 2), w_b=mat(d, 2), a_log=mat(2),
                  dt_bias=mat(2), w_g=mat(d, 16), norm_o=ones(8),
                  w_o=mat(16, d), norm1=ones(d), gate=mat(d, 24),
                  up=mat(d, 24), down=mat(24, d), norm2=ones(d))
    full = dict(w_q=mat(d, d), norm_q=ones(d), w_k=mat(d, d),
                norm_k=ones(d), w_v=mat(d, d), w_o=mat(d, d),
                norm1=ones(d), gate=mat(d, 24), up=mat(d, 24),
                down=mat(24, d), norm2=ones(d))
    tape = [mat(20, d)] + [linear[k] for k in ref.LINEAR_ARRAYS] \
        + [full[k] for k in ref.FULL_ARRAYS] + [ones(d), mat(d, 20)]
    return tape, linear, full


@pytest.mark.parametrize("neg", [True, False])
def test_the_references_delta_rule_is_the_rule_by_hand(neg):
    """One position at a time under lax.scan against the same loop in
    numpy float64, with the read BEFORE the write and the decay before
    the read."""
    import jax.numpy as jnp
    arch = _toy_arch(linear_allow_neg_eigval=neg)
    _, w, _ = _toy_weights()
    u = np.random.default_rng(1).normal(0, 1, (2, 9, 16)).astype(np.float32)
    out, final, raw = ref.linear_mixer(
        jnp.asarray(u), {k: jnp.asarray(v) for k, v in w.items()}, arch)
    heads, d_k, d_v = 2, 4, 8
    uu = u.astype(np.float64)

    def conv_silu(name):
        t = uu @ w["w_" + name]
        padded = np.pad(t, ((0, 0), (3, 0), (0, 0)))
        c = sum(w["taps_" + name][j] * padded[:, j:j + 9]
                for j in range(4))
        return c / (1 + np.exp(-c))

    def unit(t):
        t = t.reshape(2, 9, heads, d_k)
        return t / np.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)

    q, k = unit(conv_silu("q")) * d_k ** -0.5, unit(conv_silu("k"))
    v = conv_silu("v").reshape(2, 9, heads, d_v)
    alpha = np.exp(-np.exp(w["a_log"]) * np.log1p(np.exp(
        uu @ w["w_a"] + w["dt_bias"])))
    beta = (2.0 if neg else 1.0) / (1 + np.exp(-(uu @ w["w_b"])))
    state = np.zeros((2, heads, d_k, d_v))
    o = np.zeros((2, 9, heads, d_v))
    for r in range(2):
        for t in range(9):
            for h in range(heads):
                s = alpha[r, t, h] * state[r, h]
                d = beta[r, t, h] * (v[r, t, h] - s.T @ k[r, t, h])
                state[r, h] = s + np.outer(k[r, t, h], d)
                o[r, t, h] = state[r, h].T @ q[r, t, h]
    o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-6) * w["norm_o"]
    z = (uu @ w["w_g"]).reshape(2, 9, heads, d_v)
    want = (o * (z / (1 + np.exp(-z)))).reshape(2, 9, 16) @ w["w_o"]
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(final), state, rtol=2e-4,
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(raw["k"]), uu @ w["w_k"],
                               rtol=1e-5, atol=1e-6)


def test_the_reference_reads_its_tape_by_layer_kind_and_counts_it():
    tape, linear, full = _toy_weights()
    table, layers, final, head = ref.layers_of(
        tape, ["linear_attention", "full_attention"])
    assert table is tape[0] and final is tape[-2] and head is tape[-1]
    assert layers[0]["w_g"] is linear["w_g"]
    assert layers[1]["norm_k"] is full["norm_k"]
    with pytest.raises(ValueError, match="tape"):
        ref.layers_of(tape, ["linear_attention", "linear_attention"])
    assert set(ref.rates(tape, _toy_arch())) == {0}


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "paddle_tpu" not in text.replace("``paddle_tpu", "")
    assert "triangular" not in text.replace("no triangular solve", "")
    assert "lax.scan" in text


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


def _run(cfg, reduced):
    return {"reduced": reduced, "peaks": PEAKS, "config": cfg,
            "slots": 40, "kind": "serve", "window": (10.0, 14.0)}


def test_the_updates_share_and_roofline_read_the_named_calls(manifest,
                                                             cfg):
    least = arith.delta_update_seconds(40, PEAKS, **cfg)["seconds"]
    call = "delta_state_update.7 custom-call:tpu_custom_call"
    ops = [[call, 100.0 + i * 1e6, least * 1e9 / 0.8] for i in range(10)]
    ops += [["fusion.3 fusion", 50e6, 3 * sum(d for _n, _s, d in ops)],
            ["ssm_state_update.2 custom-call:tpu_custom_call", 90e6, 5e5]]
    run = _run(cfg, _Reduced(ops, 0.0, 1e9))
    roof = manifest.load_reader("delta_update_roofline_pct.serve").read(run)
    assert roof == pytest.approx(80.0)
    share = manifest.load_reader(
        "delta_update_time_share_pct.serve").read(run)
    busy = sum(d for _n, _s, d in ops)
    assert share == pytest.approx(
        100 * sum(d for n, _s, d in ops if n == call) / busy)
    # a call the window's edge clips is left out, not counted whole
    clipped = _run(cfg, _Reduced(ops + [[call, 1e9 - 10, 10.0]], 0.0, 1e9))
    assert manifest.load_reader("delta_update_roofline_pct.serve").read(
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
    granite = manifest.load_config("granite-4p0-h-micro")
    assert read(_run(granite, no_kernel)) is None


# -- the probe and the controls, at a toy size ------------------------------

def _toy_model(cfg, control=None):
    import paddle_tpu as pt
    from chipbench.drivers import serve_delta, sizes
    from paddle_tpu.serving.generation import GenerationModel
    pt.reset_default_programs()
    args, _ = sizes(cfg, {"traffic": {}}, rehearse=True)
    spec = serve_delta.build_spec(cfg, args, 4, rehearse=True)
    with serve_delta.faulty(control):
        model = GenerationModel.build(spec)
        lm = model.programs["prefill"][spec.prompt_buckets[0]]
        tape = [np.asarray(model.scope.get(p.name))
                for p in lm.main.all_parameters()]
        ctx = types.SimpleNamespace(config=cfg, seed=5, rehearse=True)
        probes = serve_delta.run_probes(ctx, model, spec)
    rounded = serve_delta.bf16_share(probes, model.state_kinds["delta"])
    return model, serve_delta.probe_errors(ctx, spec, tape, probes), rounded


@pytest.fixture(scope="module")
def sound(cfg):
    return _toy_model(cfg)


def test_the_probe_passes_the_program_as_it_is(sound):
    from chipbench.drivers import serve_delta
    _, errors, rounded = sound
    assert set(errors) == set(serve_delta.STATE_TOL)
    assert all(0 < errors[k] <= serve_delta.STATE_TOL[k]
               for k in ("kv", "conv", "delta")), errors
    assert rounded <= serve_delta.BF16_SHARE_TOL


# what each control must move at the toy size, and by how much over the
# sound program's reading: the limits themselves are set on the chip
@pytest.mark.parametrize("control,reading,least", [
    ("beta_unscaled", "delta", 0.2),
    ("read_before_decay", "delta", 0.1),
    ("pad_rows_advance", "delta", 0.2),
    ("window_shifted", "conv", 0.3),
    ("qk_not_normalised", "delta", 0.2)])
def test_a_control_is_refused_by_the_probe(cfg, sound, control, reading,
                                           least):
    from chipbench.drivers import serve_delta
    _, errors, _ = _toy_model(cfg, control)
    assert errors[reading] > max(least, 2 * sound[1][reading]), errors
    assert errors[reading] > serve_delta.STATE_TOL[reading]


def test_a_state_rounded_every_step_is_refused_whatever_its_dtype(cfg,
                                                                  sound):
    """The matrix state rounded to bfloat16 where it is stored, kept in
    its float32 arrays: the storage table passes it, the share of its
    values with nothing below eight bits does not."""
    from chipbench.drivers import serve_delta
    model, _, rounded = _toy_model(cfg, "state_bfloat16")
    assert serve_delta.storage_faults(model, cfg["storage_dtypes"]) == []
    assert rounded > 0.99 > serve_delta.BF16_SHARE_TOL > sound[2]


def test_the_controls_leave_the_registry_as_they_found_it(cfg):
    from chipbench.drivers import serve_delta
    from paddle_tpu.core.registry import OpRegistry
    from paddle_tpu.ops import delta_ops
    before = {t: OpRegistry.get(t).compute for t in (
        "gated_delta_prefill", "gated_delta_state_update",
        "causal_conv1d")}
    unit = delta_ops.unit_rows
    for control in serve_delta.CONTROLS:
        with serve_delta.faulty(control):
            pass
    assert {t: OpRegistry.get(t).compute for t in before} == before
    assert delta_ops.unit_rows is unit
    assert len(serve_delta.CONTROLS) == 6


def test_the_driver_imports_what_it_shares_with_the_other_serve_drivers():
    from chipbench.drivers import (serve, serve_delta, serve_experts,
                                   serve_state)
    for name in ("match_first_tokens", "_await", "percentile"):
        assert getattr(serve_delta, name) is getattr(serve, name)
    for name in ("_check_against_reference", "bf16_share",
                 "storage_faults", "GRACE_S", "BF16_SHARE_TOL"):
        assert getattr(serve_delta, name) is getattr(serve_state, name)
    assert serve_delta._Swapped is serve_experts._Swapped
    assert serve_delta.traffic is serve.traffic
    # the longest reply (1280 tokens) ends inside the window or the wait
    assert 40 + serve_delta.GRACE_S > 1280 * 0.03
    # one short of a bucket; one past a chunk's edge; 600 steps
    assert serve_delta.PROBES == ((127, 28), (257, 600))
    assert 0 < serve_delta.GAP_MEAN_TOL < serve_delta.GAP_MAX_TOL


ENV = dict(os.environ, JAX_PLATFORMS="cpu")
ENV.pop("JAX_COMPILATION_CACHE_DIR", None)


@pytest.mark.parametrize("trace,expect", [
    (0, {"serve_tokens_per_s", "tpot_ms_p95", "setup_s"}),
    (1, {"decode_step_ms.serve", "kv_live_share_pct.serve",
         "first_step_other_s", "compile_backend_s"})])
def test_rehearsal_of_the_cell(trace, expect):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", CELL, "--seed", "3000000058", "--seconds", "1.5",
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
    assert notes["check"]["control"] is None
    assert set(notes["state_reserved_bytes"]) == {"kv", "conv", "delta"}
    assert notes["check"]["checked_tokens"] > 0
    errors, limits = notes["check"]["state_error"], \
        notes["check"]["state_tol"]
    assert set(errors) == set(limits) == {"kv", "conv", "delta",
                                          "delta_slow"}
    assert all(0 < errors[k] <= limits[k] for k in ("kv", "conv", "delta"))
    assert notes["check"]["state_bf16_share"] \
        <= notes["check"]["bf16_share_tol"]


def test_a_sweeps_run_skips_the_probe_and_the_reference():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", CELL, "--seed", "3000000060", "--seconds", "1.5",
         "--trace", "0", "--rehearse", "--set", "skip_checks=true"],
        cwd=REPO, env=ENV, capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    # nothing was compared: such a run is never `correct`
    assert lines[-1]["correct"] is False and lines[-1]["failed"] == 0
    notes = next(ln for ln in lines if "check" in ln)
    assert notes["check"]["checked_tokens"] == 0
    assert notes["ttft_ms"]["by_third_p50"] and \
        notes["queued_at_window_end"] >= 0


def test_a_rehearsed_control_reads_not_correct():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", CELL, "--seed", "3000000059", "--seconds", "1.5",
         "--trace", "0", "--rehearse", "--set",
         'control="beta_unscaled"'],
        cwd=REPO, env=ENV, capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    assert lines[-1]["correct"] is False and lines[-1]["failed"] == 0
    notes = next(ln for ln in lines if "check" in ln)
    assert notes["check"]["control"] == "beta_unscaled"
