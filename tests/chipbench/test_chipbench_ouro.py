"""The ``ouro-2p6b`` configuration in the harness: its cell's rehearsal
end to end (with and without ``--trace``), its arithmetic — the closed
forms at the published shapes and at the cell's, against the program's
cost model —, its reader on hand-filled runs, and what its files promise
(published widths unchanged, every cut listed).

Stated discrepancy of the arithmetic, as tests/chipbench/
test_chipbench_laguna.py states it: ``arith_ouro`` counts a causal
attention as half the score matrix and no norm, rotary, softmax,
activation, exit-distribution or optimizer FLOPs; the cost model counts
a site's whole score matrix and the elementwise work, each body op once
a pass and the loop's grad op at twice the body."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import chipbench
from chipbench import arith, arith_ouro, device, trace
from chipbench.manifest import Manifest
from chipbench.spans import Collector

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    chipbench.__file__)))
MANIFEST = Manifest(REPO)
CELL = "ouro-2p6b.train-s2048"
CONFIG = MANIFEST.load_config("ouro-2p6b")
ARGS = CONFIG["builder"]["args"]
PUBLISHED = dict(ARGS, trg_vocab=49152)      # the vocabulary uncut
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
ENV.pop("JAX_COMPILATION_CACHE_DIR", None)
READER = "loop_body_time_share_pct.train"


@pytest.mark.parametrize("trace_on, expect", [
    (0, {"train_tokens_per_s", "setup_s"}),
    (1, {"input_wait_ms.train", "host_step_ms.train", "first_step_other_s",
         "compile_backend_s"}),
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
    check = next(x["check"] for x in lines if "check" in x)
    assert check["update"]["ok"] and check["update"]["descent_share"] > 0.9
    # every array is updated and scored, the shared ones among them:
    # the table, 2 layers of 11, the final norm, the head, the gate's 2
    assert check["update"]["arrays"] == check["update"]["arrays_scored"] \
        == 1 + 2 * 11 + 1 + 1 + 2


def test_published_shapes_give_the_planned_flops_per_token():
    # seven products an application: q, k, v, o, gate, up, down
    assert arith_ouro.application_params(**PUBLISHED) == 51_380_224 \
        == 4 * 2048 ** 2 + 3 * 2048 * 5632
    parts = arith_ouro.forward_flops(1, 2048, **PUBLISHED)
    per_token = {k: v / 2048 / 1e6 for k, v in parts.items()}
    assert parts["total"] == sum(v for k, v in parts.items()
                                 if k != "total")
    assert round(per_token["total"]) == 2584
    assert round(per_token["products"] + per_token["attention"]) == 1778
    assert round(per_token["heads"]) == 805
    assert per_token["gate"] == pytest.approx(4 * 2 * 2048 / 1e6)
    assert arith_ouro.train_flops(1, 2048, **PUBLISHED) == \
        3 * parts["total"]
    assert round(3 * per_token["total"] / 10) == 775       # 7.75 GFLOP
    stack = (parts["products"] + parts["attention"]) / parts["total"]
    assert round(stack * 100) == 69
    assert round(parts["heads"] / parts["total"] * 100) == 31


def test_the_cells_shapes_give_what_its_why_says():
    """Half the vocabulary (the issue's sanctioned fallback): the four
    heads cost half, the stack the same."""
    parts = arith_ouro.forward_flops(1, 2048, **ARGS)
    whole = arith_ouro.forward_flops(1, 2048, **PUBLISHED)
    assert parts["heads"] * 2 == whole["heads"]
    assert parts["products"] == whole["products"]
    assert round(parts["total"] / 2048 / 1e6) == 2181
    assert round(3 * parts["total"] / 2048 / 1e7) == 654   # 6.54 GFLOP
    stack = (parts["products"] + parts["attention"]) / parts["total"]
    assert round(stack * 100) == 82
    assert round(parts["heads"] / parts["total"] * 100) == 18
    assert round(parts["attention"] / parts["total"] * 100) == 6


def test_flash_cost_counts_a_call_a_layer_a_pass_each_way():
    assert arith_ouro.flash_calls(**ARGS) == 16       # T x L, each way
    step = arith_ouro.flash_cost(1, 2048, **ARGS)
    fwd, bwd = (arith.flash_call_cost(1, 16, 2048, 2048, 128, True, b)
                for b in (False, True))
    assert step == {"flops": 16 * (fwd["flops"] + bwd["flops"]),
                    "bytes": 16 * (fwd["bytes"] + bwd["bytes"])}
    half = arith_ouro.flash_cost(1, 2048, **dict(ARGS, total_ut_steps=2))
    assert half["flops"] * 2 == step["flops"]
    peaks = device.peaks_for("TPU v5 lite")
    assert arith.roofline_seconds(step["flops"], step["bytes"],
                                  peaks)["bound"] == "compute"


def test_train_flops_agree_with_the_cost_model():
    import paddle_tpu as pt
    from chipbench.drivers import resolve
    args = dict(ARGS, **CONFIG["rehearse"]["builder_args"])
    b, s = 2, 32
    rng = np.random.RandomState(0)
    feed = {k: rng.randint(1, args["trg_vocab"], (b, s, 1)).astype(np.int64)
            for k in ("src_ids", "trg_ids", "trg_labels")}
    feed["pos_ids"] = np.arange(s, dtype=np.int64)
    totals = {}
    for passes in (1, 4):
        pt.reset_default_programs()
        pt.reset_global_scope()
        main, startup, f = resolve(CONFIG["builder"]["function"])(
            **dict(args, max_len=s, total_ut_steps=passes))
        exe = pt.Executor()
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[f["loss"]])
        cost = exe.last_cost
        exe.close()
        ours = arith_ouro.train_flops(
            b, s, **dict(args, total_ut_steps=passes))
        # toy widths: the causal half, norms, rotary, softmax and the
        # exit distribution are a larger share than at the published
        assert 0.70 < ours / cost.flops <= 1.0, (passes, ours, cost.flops)
        body = [c for c in cost.ops if len(c.block_path) > 1]
        (loop_grad,) = [c for c in cost.ops if c.op_type == "__vjp__"
                        and "loop" in (c.note or "")]
        # the loop's grad op at twice its body over all the trips
        assert loop_grad.flops == 2 * sum(c.flops for c in body)
        sites = [c.flops for c in body
                 if c.op_type == "scaled_dot_product_attention"]
        # a site's whole score matrix, once a pass
        assert sites == [passes * b * 4 * s * s * (4 * 8 + 5)] * 2
        totals[passes] = sum(c.flops for c in body)
    pt.reset_global_scope()
    assert totals[4] == 4 * totals[1]


def _reduced(ops, t0=0.0, t1=4e9):
    plain = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": trace.OPS_LINE, "events": ops}]}]}
    return trace.Reduced(plain, 1, window_ns=(t0, t1))


def _hlo(name, opcode):
    """An operation's name as the loader keeps it (trace.short_name)."""
    return trace.short_name(f"%{name} = f32[8]{{0}} {opcode}(%x)")


def _table(ops):
    from paddle_tpu.core.op_table import OpRef, OpTable
    return OpTable("jit_step_fn", {k: OpRef(*v) for k, v in ops.items()},
                   {}, frozenset())


EMPTY = {"spans": Collector(), "window": (100.0, 104.0), "reduced": None,
         "peaks": None, "chips": 1, "kind": "train"}


@pytest.mark.parametrize("run", [
    {}, {"spans": None}, dict(EMPTY), dict(EMPTY, kind="serve"),
], ids=["empty", "smoke-call", "no-device-plane", "not-a-train-run"])
def test_the_reader_returns_none_where_there_is_nothing_to_read(run):
    assert MANIFEST.load_reader(READER).read(run) is None


def test_the_reader_returns_none_on_a_program_without_a_loop(monkeypatch):
    from chipbench import program_ops
    ops = [[_hlo("fusion.1", "fusion"), 0.0, 1e9],
           [_hlo("fusion.2", "fusion"), 1e9, 1e9]]
    straight = _table({"fusion.1": ("mul", "forward", (0,), 3),
                       "fusion.2": ("__vjp__.mul", "backward", (0,), 9)})
    monkeypatch.setattr(program_ops, "tables", lambda: iter([straight]))
    run = dict(EMPTY, reduced=_reduced(ops))
    assert MANIFEST.load_reader(READER).read(run) is None
    # and on a program that keeps no table at all (the parent's parent)
    monkeypatch.setattr(program_ops, "tables", lambda: iter(()))
    assert MANIFEST.load_reader(READER).read(
        dict(EMPTY, reduced=_reduced(ops))) is None


def test_the_reader_on_a_hand_filled_table(monkeypatch):
    from chipbench import program_ops
    ops = [[_hlo("fusion.1", "fusion"), 0.0, 0.4e9],      # embedding
           [_hlo("fusion.2", "fusion"), 0.4e9, 0.6e9],    # body, forward
           [_hlo("fusion.3", "fusion"), 1.0e9, 0.2e9],    # the loop op
           [_hlo("fusion.4", "fusion"), 1.2e9, 1.0e9],    # body, backward
           [_hlo("fusion.5", "fusion"), 2.2e9, 0.2e9],    # the grad op
           [_hlo("fusion.6", "fusion"), 2.4e9, 0.8e9],    # the heads
           [_hlo("fusion.7", "fusion"), 3.2e9, 0.8e9]]    # Adam
    looped = _table({
        "fusion.1": ("lookup_table", "forward", (0,), 1),
        "fusion.2": ("mul", "forward", (0, 1), 7),
        "fusion.3": ("static_rnn", "forward", (0,), 2),
        "fusion.4": ("__vjp__.mul", "backward", (0, 1), 7),
        "fusion.5": ("__vjp__.static_rnn", "backward", (0,), 98),
        "fusion.6": ("mul", "forward", (0,), 3),
        "fusion.7": ("adam", "optimizer", (0,), 120)})
    monkeypatch.setattr(program_ops, "tables", lambda: iter([looped]))
    run = dict(EMPTY, reduced=_reduced(ops))
    # 0.6 + 0.2 + 1.0 + 0.2 of 4.0 s busy
    assert MANIFEST.load_reader(READER).read(run) == pytest.approx(50.0)
    from chipbench.program_ops import role_share_pct
    assert role_share_pct(run, "backward") == pytest.approx(30.0)


def test_the_files_keep_every_published_width_and_list_the_cuts():
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ouro-2.6B")
    entry = MANIFEST.config_entry("ouro-2p6b")
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert set(CONFIG["reduced"]) == set(entry["reduced"])
    published = dict(hidden_size=2048, intermediate_size=5632,
                     num_attention_heads=16, num_key_value_heads=16,
                     head_dim=128, rms_norm_eps=1e-6, total_ut_steps=4)
    for key, value in published.items():
        assert CONFIG[key] == value and ARGS[key] == value, key
    assert ARGS["rope_theta"] == CONFIG["rope_theta"] == 1000000
    assert CONFIG["early_exit_threshold"] == 1          # read by nothing
    assert (CONFIG["num_hidden_layers"], ARGS["num_hidden_layers"]) == (4, 4)
    assert (CONFIG["vocab_size"], ARGS["trg_vocab"]) == (24576, 24576)
    assert CONFIG["published"] == {"num_hidden_layers": 48,
                                   "vocab_size": 49152}
    assert len(CONFIG["layer_types"]) == 48 and \
        set(CONFIG["layer_types"]) == {"full_attention"}
    assert ARGS["exit_entropy_beta"] == 0.1
    assert {"sandwich_norms", "final_norm", "exit_gate", "loss",
            "early_exit_threshold", "attention", "rope_layout",
            "initializers", "lr", "sequences"} <= set(CONFIG["assumed"])
    assert "pipeline" in CONFIG["deployment"]
    assert CONFIG["rehearse"]["builder_args"].get("total_ut_steps", 4) == 4
    if row is not None:
        assert entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert CONFIG[key] == value, key
    cell = MANIFEST.load_workload(CELL)
    assert cell["traffic"] == {"batch": 1, "seq": 2048,
                               "check_update": True,
                               "reference_chunk_tokens": 2048,
                               "gradient_chunk_tokens": 2048}
    assert MANIFEST.cell(CELL)["chips"] == 1
    assert MANIFEST.problems() == []
    listed = {m["name"] for m in MANIFEST.metrics_for(CELL, "per_layer")}
    assert {READER, "step_mfu_pct.train",
            "flash_attention_roofline_pct.train",
            "flash_fwd_time_share_pct.train",
            "flash_bwd_time_share_pct.train",
            "backward_time_share_pct.train",
            "optimizer_time_share_pct.train", "loss_time_share_pct.train",
            "op_scope_coverage_pct.train"} <= listed
    # the new reader is the manifest's last entry and this cell's alone;
    # no expert layer, no window, and the two readers that count every
    # Mosaic custom call as flash stay off it
    assert MANIFEST.data["per_layer"][-1] == {
        "name": READER, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "Executor",
        "moves": "train_tokens_per_s", "workloads": [CELL]}
    assert not [n for n in listed if n.startswith(("moe_", "flash_window_"))]
    assert not {"flash_time_share_pct.train",
                "flash_roofline_pct.train"} & listed
    assert "None" in MANIFEST.load_reader(READER).__doc__
