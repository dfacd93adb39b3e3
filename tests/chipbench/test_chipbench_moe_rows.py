"""``moe_buffer_rows.train``: the reader of the rows an expert layer's
dispatch, activation and combine ran over, beside ``moe_live_rows.train``
— on a hand-filled scope, on the tallies a program whose layer runs by
blocks leaves after its steps, and None wherever there is nothing to
read (no steps, no device plane, no such layer, the three-entry tally
of a program from before the blocks)."""
import os

import numpy as np
import pytest

import chipbench
import paddle_tpu as pt
from chipbench.manifest import Manifest
from paddle_tpu import layers
from paddle_tpu.ops import moe_ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    chipbench.__file__)))
MANIFEST = Manifest(REPO)
METRIC = "moe_buffer_rows.train"
MOE_CELLS = ["joyai-llm-flash.train-ep32", "laguna-xs2.train-s8192"]
TRACED = {"kind": "train", "steps": [(0.0, 1.0, 2.0)], "reduced": object()}


def _read(name, run):
    return MANIFEST.load_reader(name).read(run)


@pytest.fixture
def scope():
    yield pt.reset_global_scope()
    pt.reset_global_scope()


def test_the_manifest_entry_is_the_last_and_lists_the_expert_cells():
    entry = MANIFEST.data["per_layer"][-1]
    assert entry == {"name": METRIC, "unit": "rows", "better": "lower",
                     "source": "program_counter", "layer": "Kernels",
                     "moves": "train_tokens_per_s", "workloads": MOE_CELLS}
    live, = [m for m in MANIFEST.data["per_layer"]
             if m["name"] == "moe_live_rows.train"]
    assert {k: v for k, v in live.items() if k != "name"} == \
        {k: v for k, v in entry.items() if k != "name"}
    assert MANIFEST.problems() == []
    assert "None" in MANIFEST.load_reader(METRIC).__doc__


@pytest.mark.parametrize("run", [
    {}, {"spans": None}, dict(TRACED, kind="serve"), dict(TRACED, steps=[]),
    dict(TRACED, reduced=None),
], ids=["empty", "smoke-call", "not-a-train-run", "no-steps",
        "no-device-plane"])
def test_none_where_there_is_nothing_to_read(scope, run):
    scope.set("moe_experts_0.live_rows",
              np.float32([3000.0, 3.0, 1100.0, 12288.0]))
    assert _read(METRIC, TRACED) == pytest.approx(4096.0)
    assert _read(METRIC, run) is None


def test_hand_filled_scope(scope):
    """Two layers over three steps: one ran one 4,096-row block a step,
    one ran four blocks once; a parameter that only ends like a tally's
    name and a layer that ran no step are not read."""
    assert _read(METRIC, TRACED) is None            # no expert layer
    scope.set("moe_experts_0.live_rows",
              np.float32([3000.0, 3.0, 1100.0, 3 * 4096.0]))
    scope.set("moe_experts_1.live_rows",
              np.float32([9000.0, 3.0, 2000.0, 2 * 4096.0 + 16384.0]))
    scope.set("moe_experts_2.live_rows", np.zeros(4, np.float32))
    scope.set("moe_experts_1.w_0", np.ones(4, np.float32))
    assert _read(METRIC, TRACED) == pytest.approx((4096 + 8192) / 2)
    assert _read("moe_live_rows.train", TRACED) == pytest.approx(2000.0)


def test_none_on_the_three_entry_tally_of_the_parent(scope):
    scope.set("moe_experts_0.live_rows", np.float32([3000.0, 3.0, 1100.0]))
    assert _read(METRIC, TRACED) is None
    assert _read("moe_live_rows.train", TRACED) == pytest.approx(1000.0)


def _blocked_sites():
    from paddle_tpu.observability.registry import default_registry
    family = default_registry().get("paddle_tpu_moe_sites_total")
    if family is None:
        return 0
    return sum(child.value for labels, child in family.samples()
               if labels == ("row_blocks", "2", "16"))


def test_a_blocked_layers_tally_read_after_its_steps(scope):
    """A program with one expert layer (experts 6 and 7 of 16 under
    top-2, 512 tokens: 512-row blocks, 1,024 rows at the worst) run
    three steps: the reader gives the mean rows of the blocks run, by
    hand from each step's routing."""
    tokens, d, total, held, offset, k = 512, 16, 16, 2, 6, 2
    block = moe_ops.block_rows(tokens, k, held, total)
    assert (block, tokens * min(k, held)) == (512, 1024)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [tokens, d], append_batch_size=False)
        idx = layers.data("idx", [tokens, k], dtype="int32",
                          append_batch_size=False)
        w = layers.data("w", [tokens, k], append_batch_size=False)
        out = layers.moe_experts(x, idx, w, 12, total, experts_held=held,
                                 expert_offset=offset)
    exe = pt.Executor()
    exe.run(startup)
    rng = np.random.RandomState(0)
    sites = _blocked_sites()
    ran, lives = [], []
    # 70 % of the tokens pick both held experts, no held expert, or one
    for picks in ([6, 7], [0, 1], [7, 8]):
        rows = np.where(rng.rand(tokens, 1) < 0.7, [picks], [[0, 1]])
        live = int(np.sum((rows >= offset) & (rows < offset + held)))
        exe.run(main, feed={"x": rng.randn(tokens, d).astype(np.float32),
                            "idx": rows.astype(np.int32),
                            "w": rng.rand(tokens, k).astype(np.float32)},
                fetch_list=[out])
        lives.append(live)
        ran.append(-(-live // block) * block)
    assert ran == [1024, 0, 512]
    # one site traced, on the path the site counter names for blocks
    assert _blocked_sites() == sites + 1
    tally, = [np.asarray(scope.get(n)) for n in scope.local_names()
              if n.endswith(".live_rows")]
    np.testing.assert_array_equal(
        tally, [sum(lives), 3, lives[-1], sum(ran)])
    assert _read(METRIC, TRACED) == pytest.approx(sum(ran) / 3)
    assert _read("moe_live_rows.train", TRACED) == pytest.approx(
        sum(lives) / 3)
