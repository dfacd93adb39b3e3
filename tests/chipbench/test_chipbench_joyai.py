"""The ``joyai-llm-flash`` configuration in the harness: its cell's
rehearsal end to end (with and without ``--trace``), its arithmetic
against the program's cost model and XLA's ``cost_analysis``, its new
readers on hand-filled runs, and what its files promise (published
widths unchanged, the cut listed).

Stated discrepancy of the arithmetic: ``arith_joyai`` counts a causal
attention as half the score matrix, the routed experts at their
EXPECTED rows (tokens x top-k x held / total) and no norm, rotary,
softmax, activation or optimizer FLOPs; the cost model counts the full
score matrix and the elementwise work. XLA on the CPU moreover expands
a grouped product into one dense product a GROUP over the whole
worst-case buffer, which has nothing to do with the work the chip's
ragged product does: XLA is asked only where the layer holds its one
expert and every token picks it (one group, every row live), the cost
model also at a share of several experts."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import chipbench
from chipbench import arith, arith_joyai, device, trace
from chipbench.manifest import Manifest
from chipbench.spans import Collector

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    chipbench.__file__)))
MANIFEST = Manifest(REPO)
CELL = "joyai-llm-flash.train-ep32"
CONFIG = MANIFEST.load_config("joyai-llm-flash")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
ENV.pop("JAX_COMPILATION_CACHE_DIR", None)
NEW_READERS = ("step_mfu_pct.train", "moe_expert_time_share_pct.train",
               "flash_attention_roofline_pct.train", "moe_live_rows.train")


@pytest.mark.parametrize("trace_on, expect", [
    (0, {"train_tokens_per_s", "setup_s"}),
    (1, {"input_wait_ms.train", "host_step_ms.train", "first_step_other_s",
         "compile_backend_s", "moe_live_rows.train"}),
])
def test_rehearsal_of_the_cell_is_correct(trace_on, expect):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", CELL, "--seed", "3000000036", "--seconds", "1.5",
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
        assert 0 < rows < 16 * 64 * 2
    check = next(x["check"] for x in lines if "check" in x)
    assert check["update"]["ok"] and check["update"]["descent_share"] > 0.9
    # the frozen selection biases (one a routed layer: 2 at the toy
    # depth) are the arrays nobody updates, and go unscored
    assert check["update"]["arrays"] - check["update"]["arrays_scored"] == 2


def _toy(**over):
    args = dict(CONFIG["builder"]["args"])
    args.update(CONFIG["rehearse"]["builder_args"])
    args.update(over)
    return args


@pytest.mark.parametrize("b,s,ask_xla,over", [
    (2, 32, False, dict()),
    (2, 32, True, dict(n_routed_experts=1, experts_held=1,
                       num_experts_per_tok=1)),
    (1, 64, True, dict(hidden_size=64, moe_intermediate_size=32,
                       n_routed_experts=1, experts_held=1,
                       num_experts_per_tok=1, num_hidden_layers=3)),
])
def test_train_flops_agree_with_cost_model_and_xla(b, s, ask_xla, over):
    import paddle_tpu as pt
    from chipbench.drivers import resolve
    from paddle_tpu.parallel.collective_audit import aot_compiled_for
    args = _toy(**over)
    main, startup, f = resolve(CONFIG["builder"]["function"])(
        **dict(args, max_len=s))
    exe = pt.Executor()
    exe.run(startup)
    rng = np.random.RandomState(0)
    feed = {k: rng.randint(1, args["trg_vocab"], (b, s, 1)).astype(np.int64)
            for k in ("src_ids", "trg_ids", "trg_labels")}
    feed["pos_ids"] = np.arange(s, dtype=np.int64)
    exe.run(main, feed=feed, fetch_list=[f["loss"]])
    ours = arith_joyai.train_flops(b, s, **args)
    model = exe.last_cost.flops
    xla = aot_compiled_for(exe, main).cost_analysis()
    xla = (xla[0] if isinstance(xla, list) else xla)["flops"]
    exe.close()
    # toy widths: the causal half, norms, rotary and softmax are a larger
    # share than at the published widths, where matmuls are 99 %
    assert 0.75 < ours / model <= 1.0, (ours, model)
    if ask_xla:
        assert 0.70 < ours / xla <= 1.0, (ours, xla)


def test_published_shapes_give_the_planned_flops_per_token():
    args = CONFIG["builder"]["args"]
    parts = arith_joyai.forward_flops(1, 4096, **args)
    per_token = {k: v / 4096 / 1e6 for k, v in parts.items()}
    assert round(per_token["total"]) == 869
    assert round(per_token["projections"] / 6, 1) == 52.7
    assert round(per_token["attention"] / 6, 1) == 41.9
    assert round(per_token["heads"]) == 132
    # one chip's share: 8 of 256 experts see 1/32 of the routed rows
    assert per_token["routed_experts"] * 32 == pytest.approx(
        5 * 8 * 6 * 2048 * 768 / 1e6)
    assert 0.60 < (parts["projections"] + parts["attention"]) \
        / parts["total"] < 0.70
    assert arith_joyai.train_flops(1, 4096, **args) == 3 * parts["total"]


def test_flash_cost_of_two_widths():
    same = arith_joyai.flash_call_cost_two_widths(
        8, 8, 2048, 2048, 64, 64, True, False)
    assert same == arith.flash_call_cost(8, 8, 2048, 2048, 64, True, False)
    same = arith_joyai.flash_call_cost_two_widths(
        8, 8, 2048, 2048, 64, 64, False, True)
    assert same == arith.flash_call_cost(8, 8, 2048, 2048, 64, False, True)
    fwd = arith_joyai.flash_call_cost_two_widths(
        1, 32, 4096, 4096, 192, 128, True, False)
    bwd = arith_joyai.flash_call_cost_two_widths(
        1, 32, 4096, 4096, 192, 128, True, True)
    assert fwd["flops"] == 32 * 4096 * 4096 * (192 + 128)
    assert bwd["flops"] == 32 * 4096 * 4096 * (3 * 192 + 2 * 128)
    assert fwd["bytes"] == 32 * 4096 * (2 * (192 + 192 + 128 + 128) + 4)
    step = arith_joyai.flash_cost(1, 4096, **CONFIG["builder"]["args"])
    assert step["flops"] == 6 * (fwd["flops"] + bwd["flops"])
    peaks = device.peaks_for("TPU v5 lite")
    assert arith.roofline_seconds(step["flops"], step["bytes"],
                                  peaks)["bound"] == "compute"


def _reduced(ops, t0=0.0, t1=4e9):
    plain = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": trace.OPS_LINE, "events": ops}]}]}
    return trace.Reduced(plain, 1, window_ns=(t0, t1))


def _hlo(name, opcode, target=None):
    """An operation's name as the loader keeps it (trace.short_name)."""
    tail = f', custom_call_target="{target}"' if target else ""
    return trace.short_name(f"%{name} = f32[8]{{0}} {opcode}(%x){tail}")


def test_new_readers_return_none_on_an_empty_run():
    empty = {"spans": Collector(), "window": (100.0, 104.0),
             "reduced": None, "peaks": None, "chips": 1, "kind": "train"}
    for name in NEW_READERS:
        assert MANIFEST.load_reader(name).read(dict(empty)) is None, name
    # a device plane without the kernels (the parent's program): nothing
    # to read, and no raise
    bare = dict(empty, reduced=_reduced([[_hlo("fusion.1", "fusion"),
                                          0.0, 1e9]]),
                peaks=device.peaks_for("TPU v5 lite"))
    for name in NEW_READERS:
        assert MANIFEST.load_reader(name).read(dict(bare)) is None, name


def test_new_readers_on_a_hand_filled_trace():
    call = "tpu_custom_call"
    ops = [[_hlo("jvp_flash_fwd_.1", "custom-call", call), 0.0, 0.2e9],
           [_hlo("transpose_jvp_flash_bwd_dq_.1", "custom-call", call),
            0.2e9, 0.3e9],
           [_hlo("transpose_jvp_flash_bwd_dkv_.1", "custom-call", call),
            0.5e9, 0.3e9],
           [_hlo("ragged-dot-none.7", "custom-call", call), 1.0e9, 0.1e9],
           [_hlo("ragged-dot-metadata", "custom-call", call), 1.1e9,
            0.02e9],
           [_hlo("sort.3", "sort"), 1.2e9, 0.08e9],
           [_hlo("fusion.9", "fusion"), 1.3e9, 1.0e9]]
    peaks = device.peaks_for("TPU v5 lite")
    steps = [(100.0, 102.0, 9.7), (102.0, 104.0, 9.6)]
    cost = {"flops": 0.08 * peaks["bf16_flops_per_s"], "bytes": 1.0}
    run = {"spans": Collector(), "window": (100.0, 104.0),
           "reduced": _reduced(ops), "peaks": peaks, "chips": 1,
           "kind": "train", "steps": steps, "flash_cost": cost,
           "step_flops": 0.25 * 2.0 * peaks["bf16_flops_per_s"]}
    import paddle_tpu as pt
    scope = pt.reset_global_scope()
    scope.set("moe_experts_0.live_rows", np.float32([3000.0, 3.0, 1100.0]))
    scope.set("moe_experts_1.live_rows", np.float32([2400.0, 3.0, 700.0]))
    scope.set("moe_experts_1.w_0", np.ones(3, np.float32))
    read = {n: MANIFEST.load_reader(n).read(run) for n in NEW_READERS}
    pt.reset_global_scope()
    assert read["moe_live_rows.train"] == pytest.approx(900.0)
    assert read["moe_expert_time_share_pct.train"] == pytest.approx(
        0.2 / 2.0 * 100)
    # two steps' least time (0.16 s) over the NAMED flash kernels' 0.8 s:
    # the ragged-dot Mosaic calls are not in the divisor
    assert read["flash_attention_roofline_pct.train"] == pytest.approx(20.0)
    assert read["step_mfu_pct.train"] == pytest.approx(25.0)


def test_the_files_keep_every_published_width_and_list_the_cut():
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "JoyAI-LLM-Flash")
    entry = MANIFEST.config_entry("joyai-llm-flash")
    assert sorted(entry["reduced"]) == ["n_routed_experts",
                                        "num_hidden_layers", "vocab_size"]
    assert set(CONFIG["reduced"]) == set(entry["reduced"])
    published = dict(
        hidden_size=2048, num_attention_heads=32, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, intermediate_size=7168, moe_intermediate_size=768,
        num_experts_per_tok=8, n_shared_experts=1,
        routed_scaling_factor=2.5, scoring_func="sigmoid",
        rope_theta=32000000, num_nextn_predict_layers=1,
        rms_norm_eps=1e-6, first_k_dense_replace=1, norm_topk_prob=True,
        rope_interleave=True)
    args = CONFIG["builder"]["args"]
    for key, value in published.items():
        assert CONFIG[key] == value and args[key] == value, key
    assert args["n_routed_experts"] == 256          # the router's width
    assert (CONFIG["n_routed_experts"], args["experts_held"]) == (8, 8)
    assert (CONFIG["num_hidden_layers"], args["num_hidden_layers"]) == (5, 5)
    assert (CONFIG["vocab_size"], args["trg_vocab"]) == (16160, 16160)
    assert CONFIG["published"] == {"num_hidden_layers": 40,
                                   "n_routed_experts": 256,
                                   "vocab_size": 129280}
    assert {"mtp_loss_weight", "mtp_concatenation", "mtp_hidden_state",
            "selection_bias", "dropout", "sequences"} <= \
        set(CONFIG["assumed"])
    if row is not None:
        assert entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert CONFIG[key] == value, key
    cell = MANIFEST.load_workload(CELL)
    assert cell["traffic"] == {"batch": 1, "seq": 4096,
                               "check_update": True,
                               "reference_chunk_tokens": 4096,
                               "gradient_chunk_tokens": 4096}
    assert MANIFEST.problems() == []
    listed = {m["name"] for m in MANIFEST.metrics_for(CELL, "per_layer")}
    assert set(NEW_READERS) <= listed
    # every Mosaic custom call is counted as flash by these two, and the
    # grouped expert products are Mosaic calls: the cell stays out
    assert not {"flash_time_share_pct.train",
                "flash_roofline_pct.train"} & listed
