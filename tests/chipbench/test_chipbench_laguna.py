"""The ``laguna-xs2`` configuration in the harness: its cell's rehearsal
end to end (with and without ``--trace``), its arithmetic — the parts,
the band against a brute-force count of visible pairs, against the
program's cost model — its two new readers on hand-filled runs, and what
its files promise (published widths unchanged, the cut listed).

Stated discrepancy of the arithmetic, as tests/chipbench/
test_chipbench_joyai.py states it for the other expert configuration:
``arith_laguna`` counts a causal attention as half the score matrix, a
windowed one at its band, the routed experts at their expected rows and
no norm, rotary, softmax, gate product, activation or optimizer FLOPs;
the cost model counts a full site's whole score matrix, a windowed
site's band, and the elementwise work."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import chipbench
from chipbench import arith, arith_laguna, device, trace
from chipbench.manifest import Manifest
from chipbench.spans import Collector

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    chipbench.__file__)))
MANIFEST = Manifest(REPO)
CELL = "laguna-xs2.train-s8192"
CONFIG = MANIFEST.load_config("laguna-xs2")
ARGS = CONFIG["builder"]["args"]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
ENV.pop("JAX_COMPILATION_CACHE_DIR", None)
NEW_READERS = ("flash_window_time_share_pct.train",
               "flash_window_roofline_pct.train")


@pytest.mark.parametrize("trace_on, expect", [
    (0, {"train_tokens_per_s", "setup_s"}),
    (1, {"input_wait_ms.train", "host_step_ms.train", "first_step_other_s",
         "compile_backend_s", "moe_live_rows.train"}),
])
def test_rehearsal_of_the_cell_is_correct(trace_on, expect):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", CELL, "--seed", "3000000041", "--seconds", "1.5",
         "--trace", str(trace_on), "--rehearse"],
        cwd=REPO, env=ENV, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    line = lines[-1]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    # off the chip the device-trace metrics and the share of a peak are
    # left out of the line, never reported from host numbers
    assert set(line["metrics"]) == expect
    if trace_on:
        # 2 of 8 experts held under top-2: at most 2 of a token's picks
        rows = line["metrics"]["moe_live_rows.train"]["value"]
        assert 0 < rows < 4 * 64 * 2
    check = next(x["check"] for x in lines if "check" in x)
    assert check["update"]["ok"] and check["update"]["descent_share"] > 0.9
    # no selection bias: every array is updated and scored
    assert check["update"]["arrays"] == check["update"]["arrays_scored"]


@pytest.mark.parametrize("seq,window", [(64, 8), (64, 64), (64, 100),
                                        (100, 7), (33, 1), (8192, 512)])
def test_the_band_is_a_brute_force_count_of_visible_pairs(seq, window):
    booked = arith_laguna.visible_pairs(seq, window)
    if window >= seq:           # plain causal: arith.py's half matrix
        assert booked == seq * seq / 2
        return
    q, k = np.arange(seq)[:, None], np.arange(seq)[None, :]
    assert booked == int(((k <= q) & (k > q - window)).sum())


def test_published_shapes_give_the_planned_flops_per_token():
    parts = arith_laguna.forward_flops(1, 8192, **ARGS)
    per_token = {k: v / 8192 / 1e6 for k, v in parts.items()}
    assert parts["total"] == sum(v for k, v in parts.items()
                                 if k != "total")
    assert round(per_token["total"]) == 783
    assert round(per_token["projections"]) == 345
    # two full layers at 100.7 each, three window layers at 16.3 each
    assert per_token["attention"] == pytest.approx(
        2 * 100.66 + 3 * 16.25, rel=1e-3)
    assert round(per_token["dense_ffn"], 1) == 100.7
    assert round(per_token["heads"], 1) == 51.4
    # one chip's share: 8 of 256 experts see 1/32 of the routed rows
    assert per_token["routed_experts"] * 32 == pytest.approx(
        4 * 8 * 6 * 2048 * 512 / 1e6)
    assert arith_laguna.train_flops(1, 8192, **ARGS) == 3 * parts["total"]
    # without the walk's lower bound each window layer would cost what a
    # causal one does: +354 MFLOP a token, 45 % on top of the step
    plain = arith_laguna.forward_flops(
        1, 8192, **dict(ARGS, sliding_window=8192))
    extra = (plain["total"] - parts["total"]) / 8192 / 1e6
    assert round(extra) == 354 and 0.44 < extra / per_token["total"] < 0.46


def test_flash_cost_counts_the_band_and_k_and_v_at_their_own_heads():
    step = arith_laguna.flash_cost(1, 8192, **ARGS)
    full_f = arith_laguna.flash_call_cost(1, 48, 8, 8192, 128,
                                          8192 * 8192 / 2, False)
    assert full_f == {
        "flops": 4.0 * 48 * 8192 * 8192 / 2 * 128,
        "bytes": 2 * 8192 * 128 * (2 * 48 + 2 * 8) + 48 * 8192 * 4}
    # at equal head counts and no window: arith.py's own call
    assert arith_laguna.flash_call_cost(
        8, 8, 8, 2048, 64, 2048 * 2048 / 2, True) == \
        arith.flash_call_cost(8, 8, 2048, 2048, 64, True, True)
    band = arith_laguna.visible_pairs(8192, 512)
    win = [arith_laguna.flash_call_cost(1, 64, 8, 8192, 128, band, b)
           for b in (False, True)]
    full = [arith_laguna.flash_call_cost(1, 48, 8, 8192, 128,
                                         8192 * 8192 / 2, b)
            for b in (False, True)]
    for key in ("flops", "bytes"):
        assert step["window_" + key] == 3 * sum(c[key] for c in win)
        assert step[key] == step["window_" + key] \
            + 2 * sum(c[key] for c in full)
    peaks = device.peaks_for("TPU v5 lite")
    assert arith.roofline_seconds(step["flops"], step["bytes"],
                                  peaks)["bound"] == "compute"
    assert arith.roofline_seconds(step["window_flops"],
                                  step["window_bytes"],
                                  peaks)["bound"] == "compute"


def test_train_flops_agree_with_the_cost_model():
    import paddle_tpu as pt
    from chipbench.drivers import resolve
    args = dict(ARGS, **CONFIG["rehearse"]["builder_args"])
    b, s = 2, 32
    main, startup, f = resolve(CONFIG["builder"]["function"])(
        **dict(args, max_len=s))
    exe = pt.Executor()
    exe.run(startup)
    rng = np.random.RandomState(0)
    feed = {k: rng.randint(1, args["trg_vocab"], (b, s, 1)).astype(np.int64)
            for k in ("src_ids", "trg_ids", "trg_labels")}
    feed["pos_ids"] = np.arange(s, dtype=np.int64)
    exe.run(main, feed=feed, fetch_list=[f["loss"]])
    ours = arith_laguna.train_flops(b, s, **args)
    model = exe.last_cost.flops
    by_site = [c.flops for c in exe.last_cost.ops
               if c.op_type == "scaled_dot_product_attention"]
    exe.close()
    pt.reset_global_scope()
    # toy widths: the causal half, norms, rotary, softmax and the gates
    # are a larger share than at the published widths
    assert 0.70 < ours / model <= 1.0, (ours, model)
    # the cost model books the two windowed sites (8 heads, window 8)
    # at their band and the full one (6 heads) at its score matrix
    band = 8 * 9 // 2 + (s - 8) * 8
    assert by_site == [b * 6 * s * s * (2 * 32 + 5),
                       b * 8 * band * (2 * 32 + 5),
                       b * 8 * band * (2 * 32 + 5)]


def _reduced(ops, t0=0.0, t1=4e9):
    plain = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": trace.OPS_LINE, "events": ops}]}]}
    return trace.Reduced(plain, 1, window_ns=(t0, t1))


def _hlo(name, opcode, target=None):
    """An operation's name as the loader keeps it (trace.short_name)."""
    tail = f', custom_call_target="{target}"' if target else ""
    return trace.short_name(f"%{name} = f32[8]{{0}} {opcode}(%x){tail}")


def test_new_readers_return_none_where_there_is_nothing_to_read():
    empty = {"spans": Collector(), "window": (100.0, 104.0),
             "reduced": None, "peaks": None, "chips": 1, "kind": "train"}
    for name in NEW_READERS:
        assert MANIFEST.load_reader(name).read(dict(empty)) is None, name
    # a device plane whose flash kernels have no window (the parent's
    # program, the other configurations) under a cost that books none
    peaks = device.peaks_for("TPU v5 lite")
    plain = dict(empty, peaks=peaks, steps=[(100.0, 102.0, 9.7)],
                 flash_cost={"flops": 1e12, "bytes": 1e9},
                 reduced=_reduced([[_hlo(
                     "jvp_flash_fwd_.1", "custom-call", "tpu_custom_call"),
                     0.0, 1e9]]))
    for name in NEW_READERS:
        assert MANIFEST.load_reader(name).read(dict(plain)) is None, name
    # the kernels there and a cost without the windowed keys: the share
    # of time reads, the share of a roofline has nothing to divide
    named = dict(plain, reduced=_reduced([[_hlo(
        "jvp_flash_fwd_window_.1", "custom-call", "tpu_custom_call"),
        0.0, 1e9]]))
    assert MANIFEST.load_reader(NEW_READERS[0]).read(dict(named)) == \
        pytest.approx(100.0)
    assert MANIFEST.load_reader(NEW_READERS[1]).read(dict(named)) is None


def test_new_readers_on_a_hand_filled_trace():
    call = "tpu_custom_call"
    ops = [[_hlo("jvp_flash_fwd_window_.1", "custom-call", call), 0.0,
            0.1e9],
           [_hlo("jvp_flash_fwd_window_.2", "custom-call", call), 0.1e9,
            0.1e9],
           [_hlo("jvp_flash_bwd_dkv_dq_window_.1", "custom-call", call),
            0.2e9, 0.2e9],
           [_hlo("jvp_flash_fwd_.1", "custom-call", call), 0.4e9, 0.3e9],
           [_hlo("jvp_flash_bwd_dkv_dq_.3", "custom-call", call), 0.7e9,
            0.3e9],
           [_hlo("ragged-dot-none.7", "custom-call", call), 1.0e9, 0.1e9],
           [_hlo("fusion.9", "fusion"), 1.1e9, 0.9e9]]
    peaks = device.peaks_for("TPU v5 lite")
    steps = [(100.0, 102.0, 9.7), (102.0, 104.0, 9.6)]
    cost = {"flops": 0.2 * peaks["bf16_flops_per_s"], "bytes": 1.0,
            "window_flops": 0.05 * peaks["bf16_flops_per_s"],
            "window_bytes": 1.0}
    run = {"spans": Collector(), "window": (100.0, 104.0),
           "reduced": _reduced(ops), "peaks": peaks, "chips": 1,
           "kind": "train", "steps": steps, "flash_cost": cost}
    read = {n: MANIFEST.load_reader(n).read(run)
            for n in NEW_READERS + ("flash_attention_roofline_pct.train",
                                    "flash_fwd_time_share_pct.train",
                                    "flash_bwd_time_share_pct.train")}
    # the windowed calls' 0.4 s of 2.0 s busy
    assert read["flash_window_time_share_pct.train"] == pytest.approx(20.0)
    # two steps' least time for the windowed sites (0.1 s) over 0.4 s
    assert read["flash_window_roofline_pct.train"] == pytest.approx(25.0)
    # the accepted readers count the windowed calls among all of them
    assert read["flash_attention_roofline_pct.train"] == pytest.approx(
        2 * 0.2 / 1.0 * 100)
    assert read["flash_fwd_time_share_pct.train"] == pytest.approx(25.0)
    assert read["flash_bwd_time_share_pct.train"] == pytest.approx(25.0)


def test_the_files_keep_every_published_width_and_list_the_cut():
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Laguna-XS.2")
    entry = MANIFEST.config_entry("laguna-xs2")
    assert sorted(entry["reduced"]) == ["num_experts", "num_hidden_layers",
                                        "vocab_size"]
    assert set(CONFIG["reduced"]) == set(entry["reduced"])
    published = dict(
        hidden_size=2048, intermediate_size=8192, moe_intermediate_size=512,
        shared_expert_intermediate_size=512, num_experts_per_tok=8,
        num_key_value_heads=8, head_dim=128, sliding_window=512,
        rms_norm_eps=1e-6, gating=True)
    for key, value in published.items():
        assert CONFIG[key] == value and ARGS[key] == value, key
    assert ARGS["routed_scaling_factor"] == \
        CONFIG["moe_routed_scaling_factor"] == 2.5
    assert ARGS["n_routed_experts"] == 256          # the router's width
    assert (CONFIG["num_experts"], ARGS["experts_held"]) == (8, 8)
    assert (CONFIG["num_hidden_layers"], ARGS["num_hidden_layers"]) == (5, 5)
    assert (CONFIG["vocab_size"], ARGS["trg_vocab"]) == (12544, 12544)
    assert CONFIG["published"] == {"num_hidden_layers": 40,
                                   "num_experts": 256,
                                   "vocab_size": 100352}
    # the leading dense full-attention layer, then one whole period
    for key in ("layer_types", "num_attention_heads_per_layer",
                "mlp_layer_types"):
        assert len(CONFIG[key]) == 40 and ARGS[key] == CONFIG[key][:5], key
    assert ARGS["layer_types"] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert ARGS["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert ARGS["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    for kind in ("full_attention", "sliding_attention"):
        assert ARGS["rope_parameters"][kind] == \
            CONFIG["rope_parameters"][kind]
    assert {"gating", "router", "hidden_act", "qk_norm", "rope_layout",
            "blocks", "initializers", "lr", "sequences"} <= \
        set(CONFIG["assumed"])
    assert "32 chips" in CONFIG["deployment"]
    if row is not None:
        assert entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert CONFIG[key] == value, key
    cell = MANIFEST.load_workload(CELL)
    assert cell["traffic"] == {"batch": 1, "seq": 8192,
                               "check_update": True,
                               "reference_chunk_tokens": 8192,
                               "gradient_chunk_tokens": 8192}
    assert MANIFEST.cell(CELL)["chips"] == 1
    assert MANIFEST.problems() == []
    listed = {m["name"] for m in MANIFEST.metrics_for(CELL, "per_layer")}
    assert set(NEW_READERS) <= listed
    assert {"step_mfu_pct.train", "flash_attention_roofline_pct.train",
            "moe_live_rows.train", "backward_time_share_pct.train",
            "op_scope_coverage_pct.train"} <= listed
    # the new readers are this cell's alone, and the two that count every
    # Mosaic custom call as flash stay off it
    for m in MANIFEST.data["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["layer"] == "Kernels"
    assert not {"flash_time_share_pct.train",
                "flash_roofline_pct.train"} & listed
