"""Static memory planner (analysis/memory.py) + the executor's
pre-compile OOM gate: liveness intervals, arena/ideal peaks, which of
the two the gate judges, budget diagnostics, flags, and metric
publication."""
import json

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, optimizer
from paddle_tpu.analysis import memory, verify_program
from paddle_tpu.analysis.diagnostics import VerificationError


def _mlp(hidden=(64, 64), train=True):
    """3-layer MLP train graph: enough distinct activation intervals
    for the two peaks to differ, small enough to hand-check."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [32])
        y = layers.data("y", [1])
        h = x
        for width in hidden:
            h = layers.fc(h, size=width, act="relu")
        pred = layers.fc(h, size=1)
        loss = layers.mean(layers.square(
            layers.elementwise_sub(pred, y)))
        if train:
            optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return main, startup, loss


# ---------------------------------------------------------------------------
# flags
# ---------------------------------------------------------------------------
def test_memory_flags_registered():
    from paddle_tpu import flags
    assert flags.FLAGS["PADDLE_TPU_HBM_BYTES"][0] == str(16 * 1024 ** 3)


def test_hbm_budget_env(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_HBM_BYTES", raising=False)
    assert memory.hbm_budget_bytes() == memory.DEFAULT_HBM_BYTES
    monkeypatch.setenv("PADDLE_TPU_HBM_BYTES", "1000000")
    assert memory.hbm_budget_bytes() == 1000000
    monkeypatch.setenv("PADDLE_TPU_HBM_BYTES", "0")
    assert memory.hbm_budget_bytes() == 0
    monkeypatch.setenv("PADDLE_TPU_HBM_BYTES", "not-a-number")
    assert memory.hbm_budget_bytes() == memory.DEFAULT_HBM_BYTES


# ---------------------------------------------------------------------------
# liveness + peak accounting
# ---------------------------------------------------------------------------
def test_liveness_intervals_and_byte_accounting():
    main, _startup, _loss = _mlp(train=False)
    rep = memory.program_memory(main, batch=4,
                                feed_names=["x", "y"])
    by_name = {v.name: v for v in rep.intervals}
    # feeds materialize before op 0 with -1 bound to batch
    assert by_name["x"].first == 0
    assert by_name["x"].bytes == 4 * 32 * 4
    # params are resident for the whole step
    w = by_name["fc_0.w_0"]
    assert w.kind == "resident"
    assert (w.first, w.last) == (0, rep.n_ops - 1)
    assert w.bytes == 32 * 64 * 4
    # every interval is sane and the totals tie out
    for v in rep.intervals:
        assert 0 <= v.first <= v.last <= rep.n_ops - 1, v.name
    assert rep.peak_bytes == rep.resident_bytes + rep.activation_bytes
    assert rep.peak_bytes == sum(v.bytes for v in rep.intervals)


def test_ideal_peak_bounded_by_arena_peak():
    main, _startup, _loss = _mlp()
    rep = memory.program_memory(main, batch=4, feed_names=["x", "y"])
    assert 0 < rep.ideal_peak_bytes <= rep.peak_bytes
    assert rep.resident_bytes <= rep.ideal_peak_bytes
    # report surfaces are well-formed
    d = rep.to_dict(top_k=5)
    assert len(d["top"]) == 5
    assert d["high_water"]["op_index"] >= 0
    json.loads(rep.to_json())
    assert "peak" in rep.table()


def test_memory_pass_attaches_report_to_verify():
    main, startup, loss = _mlp()
    rep = verify_program(main, startup=startup, feed_names=["x", "y"],
                         fetch_names=[loss.name],
                         passes=[memory.MemoryPass(batch=4)])
    assert rep.memory is not None
    assert rep.memory.peak_bytes > 0


# ---------------------------------------------------------------------------
# pre-compile OOM gate
# ---------------------------------------------------------------------------
def test_check_budget_diagnostic_structure():
    main, _startup, _loss = _mlp()
    rep = memory.program_memory(main, batch=4, feed_names=["x", "y"])
    vr = memory.check_budget(rep, budget=1)
    assert not vr.ok
    d = vr.by_code("hbm-oom")[0]
    assert d.op_index == rep.high_water["op_index"]
    assert "PADDLE_TPU_HBM_BYTES" in d.hint
    # top offenders are named with their sizes
    assert rep.top(1)[0].name in d.message
    # a zero/absent budget never errors
    assert memory.check_budget(rep, budget=0).ok
    assert memory.check_budget(rep, budget=rep.peak_bytes).ok


def test_gate_judges_the_free_at_last_use_peak():
    """The budget is held against `ideal_peak_bytes`; the arena figure
    (no buffer freed in the step) stays in the report and the message."""
    main, _startup, _loss = _mlp()
    rep = memory.program_memory(main, batch=4, feed_names=["x", "y"])
    assert rep.ideal_peak_bytes < rep.peak_bytes
    between = (rep.ideal_peak_bytes + rep.peak_bytes) // 2
    assert memory.check_budget(rep, budget=between).ok
    assert memory.check_budget(rep, budget=rep.ideal_peak_bytes).ok
    vr = memory.check_budget(rep, budget=rep.ideal_peak_bytes - 1)
    assert not vr.ok
    msg = vr.by_code("hbm-oom")[0].message
    assert memory._fmt_bytes(rep.ideal_peak_bytes) in msg
    assert memory._fmt_bytes(rep.peak_bytes) in msg
    assert vr.memory is rep


def test_gate_number_ignores_var_names():
    """Writing an op's output under the name of the input that dies
    there (what a buffer-reuse renaming does) takes one buffer off the
    arena figure and leaves the number the gate judges where it was,
    give or take that one buffer at the op where the two meet."""
    main, _startup, _loss = _mlp(train=False)
    feeds = ["x", "y"]
    rep = memory.program_memory(main, batch=4, feed_names=feeds)
    renamed = main.clone()
    ops = renamed.desc.blocks[0].ops
    at = next(i for i, op in enumerate(ops) if op.type == "relu")
    (src,), (dst,) = ops[at].input("X"), ops[at].output("Out")
    assert not any(src in op.input_names() for op in ops[at + 1:])
    for op in ops[at:]:
        for names in list(op.inputs.values()) + list(op.outputs.values()):
            names[:] = [src if n == dst else n for n in names]
    rep2 = memory.program_memory(renamed, batch=4, feed_names=feeds)
    freed = {v.name: v for v in rep.intervals}[dst].bytes
    assert freed > 0
    assert rep2.peak_bytes == rep.peak_bytes - freed
    assert abs(rep2.ideal_peak_bytes - rep.ideal_peak_bytes) <= freed
    budget = rep.ideal_peak_bytes + freed
    assert budget < rep2.peak_bytes
    assert memory.check_budget(rep, budget=budget).ok
    assert memory.check_budget(rep2, budget=budget).ok


def test_executor_gate_raises_before_compile(monkeypatch):
    main, startup, loss = _mlp()
    exe = pt.Executor()
    scope = pt.Scope()
    feed = {"x": np.random.rand(4, 32).astype(np.float32),
            "y": np.random.rand(4, 1).astype(np.float32)}
    with pt.scope_guard(scope):
        exe.run(startup)
        # tighten the budget AFTER startup so only the train program
        # (whose resident params alone blow 128 B) hits the gate
        monkeypatch.setenv("PADDLE_TPU_HBM_BYTES", "128")
        with pytest.raises(VerificationError) as ei:
            exe.run(main, feed=feed, fetch_list=[loss])
    msg = str(ei.value)
    assert "hbm-oom" in msg and "pre-compile memory gate" in msg
    # nothing was cached for this program: raising the budget lets the
    # same executor compile and run the same program
    monkeypatch.setenv("PADDLE_TPU_HBM_BYTES", "0")
    with pt.scope_guard(scope):
        out = exe.run(main, feed=feed, fetch_list=[loss])
    assert np.isfinite(float(np.ravel(np.asarray(out[0]))[0]))
    assert exe.last_memory is not None
    assert exe.last_memory.peak_bytes > 0


def test_run_result_carries_memory_report():
    main, startup, loss = _mlp()
    exe = pt.Executor()
    scope = pt.Scope()
    feed = {"x": np.random.rand(4, 32).astype(np.float32),
            "y": np.random.rand(4, 1).astype(np.float32)}
    with pt.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
    mem = exe.last_memory
    assert mem is not None
    # the gate planned the program with REAL feed shapes: the fed
    # batch of 4 is bound, not the declared -1
    by_name = {v.name: v for v in mem.intervals}
    assert by_name["x"].bytes == 4 * 32 * 4


# ---------------------------------------------------------------------------
# metric publication
# ---------------------------------------------------------------------------
def test_publish_peak_gauge():
    from paddle_tpu.observability.registry import default_registry
    memory.publish_peak("planner_test", 12345)
    fam = default_registry().get("paddle_tpu_memory_peak_bytes")
    vals = {key: g.value for key, g in fam.samples()}
    assert vals[("planner_test",)] == 12345.0
