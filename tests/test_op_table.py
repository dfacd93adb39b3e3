"""Device time by program op: the scope `_trace_ops` gives every op, the
table a cache entry builds from its compiled module when asked
(core/op_table.py, CompiledProgram.op_table), the process-wide list of
entries, and the reduction of a device trace through the tables
(profiler.op_times), on a synthetic trace in the plain form."""
import collections
import gc
import re
import subprocess
import sys
import threading
import types

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu.core import executor as ex
from paddle_tpu.core import op_table
from paddle_tpu.core.op_table import OpRef, OpTable
from paddle_tpu.observability import default_registry


def _program(optimizer="adam"):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        pred = layers.fc(layers.fc(x, size=16, act="relu"), size=1)
        loss = layers.reduce_mean(layers.square_error_cost(pred, y))
        opt = {"adam": pt.optimizer.AdamOptimizer,
               "sgd": pt.optimizer.SGDOptimizer}[optimizer]
        opt(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _feed(batch=16):
    rng = np.random.RandomState(0)
    return {"x": rng.randn(batch, 8).astype(np.float32),
            "y": rng.randn(batch, 1).astype(np.float32)}


def _entry(exe, main):
    (entry,) = [c for k, c in exe._cache.items() if k[0] == main.desc.uid]
    return entry


@pytest.fixture
def trained():
    main, startup, loss = _program()
    exe = pt.Executor()
    exe.run(startup)
    exe.run(main, feed=_feed(), fetch_list=[loss])
    return exe, main, loss


def _site_counters():
    out = {}
    for fam in default_registry().families():
        if fam.name.endswith("_sites_total"):
            out.update({(fam.name,) + k: c.value
                        for k, c in fam.samples()})
    return out


# -- the table of a compiled train program ----------------------------------

def test_every_named_instruction_maps_to_an_op_of_the_program(trained):
    exe, main, _loss = trained
    entry = _entry(exe, main)
    table = entry.op_table()
    assert table.module == "jit_step_fn"
    ops = main.desc.blocks[0].ops
    text = entry.lower_again().as_text()
    named = 0
    for line in text.splitlines():
        m = re.match(r"^\s+(?:ROOT )?%?([^\s=]+) = .*"
                     r'op_name="(jit\(step_fn\)/[^"]*)"', line)
        if not m or m.group(2).startswith("jit(step_fn)/step_counter/"):
            continue   # the one instruction that is no op's: step + 1
        named += 1
        ref = table.ops[m.group(1)]
        assert ref.block_path == (0,)
        op = ops[ref.op_index]
        assert op_table.scope_type(op) == ref.op_type
        # the type is the FIRST component, the op the second
        assert m.group(2).split(";")[0].startswith(
            f"jit(step_fn)/{ref.op_type}/b0.{ref.op_index}")
    assert named > 50
    assert not set(table.ops) & set(table.unmapped)


@pytest.mark.parametrize("role, types", [
    ("forward", {"mul", "elementwise_add", "relu", "square_error_cost",
                 "reduce_mean"}),
    ("backward", {"__vjp__.mul", "__vjp__.elementwise_add",
                  "__vjp__.relu", "__vjp__.square_error_cost"}),
    ("optimizer", {"adam"}),
])
def test_roles(trained, role, types):
    exe, main, _loss = trained
    refs = set(_entry(exe, main).op_table().ops.values())
    got = {r.op_type for r in refs if r.role == role}
    assert types <= got, got
    if role == "backward":
        assert all(t.startswith("__vjp__.") for t in got)
    if role == "optimizer":
        assert got == {"adam"}


def test_two_ops_of_one_type_have_two_indices(trained):
    exe, main, _loss = trained
    refs = set(_entry(exe, main).op_table().ops.values())
    ops = main.desc.blocks[0].ops
    for op_type in ("mul", "adam", "__vjp__.mul"):
        indices = {r.op_index for r in refs if r.op_type == op_type}
        assert len(indices) >= 2, (op_type, indices)
        assert all(op_table.scope_type(ops[i]) == op_type
                   for i in indices)


def test_what_xla_gives_no_op_name_is_unmapped_not_guessed(trained):
    exe, main, _loss = trained
    table = _entry(exe, main).op_table()
    assert "parameter" in set(table.unmapped.values())
    assert all(isinstance(r, OpRef) for r in table.ops.values())


def test_ops_after_the_first_grad_op_that_are_neither_are_other():
    main, startup, loss = _program("sgd")
    ops = main.desc.blocks[0].ops
    first = next(i for i, op in enumerate(ops) if op.type == "__vjp__")
    kinds = op_table.program_ops(main.desc.blocks[0])
    assert kinds[(0, first)][0] == "backward"
    assert all(kinds[(0, i)][0] == "forward" for i in range(first))
    assert {kinds[(0, i)] for i, op in enumerate(ops)
            if op.type == "sgd"} == {("optimizer", "sgd")}
    later = {kinds[(0, i)][0] for i, op in enumerate(ops)
             if i > first and op.type not in ("__vjp__", "sgd")}
    assert later <= {"other"}


# -- built on the first ask, never before -----------------------------------

def test_run_on_a_miss_and_on_a_hit_leaves_the_table_unbuilt():
    main, startup, loss = _program()
    exe = pt.Executor()
    exe.run(startup)
    exe.run(main, feed=_feed(), fetch_list=[loss])       # a miss
    entry = _entry(exe, main)
    assert entry._op_table is None and entry.avals is not None
    exe.run(main, feed=_feed(), fetch_list=[loss])       # a hit
    assert exe.cache_stats["hits"] >= 1
    assert entry._op_table is None
    assert all(e._op_table is None for e in ex.compiled_programs()
               if e.uid in (main.desc.uid, startup.desc.uid))


def test_a_second_ask_builds_nothing(trained, monkeypatch):
    exe, main, _loss = trained
    entry = _entry(exe, main)
    calls = []
    real = entry.lower_again
    monkeypatch.setattr(entry, "lower_again",
                        lambda: calls.append(1) or real())
    first = entry.op_table()
    assert entry.op_table() is first and calls == [1]


def test_an_ask_runs_no_op_rule_again(trained):
    exe, main, _loss = trained
    before = _site_counters()
    assert any(k[0] == "paddle_tpu_grad_sites_total" for k in before)
    _entry(exe, main).op_table()
    assert _site_counters() == before


def test_the_entry_keeps_shapes_and_no_arrays(trained):
    import jax
    exe, main, _loss = trained
    entry = _entry(exe, main)
    leaves = jax.tree_util.tree_leaves(entry.avals)
    assert leaves and all(isinstance(a, jax.ShapeDtypeStruct)
                          for a in leaves)
    feed, ro, rw, _step = entry.avals
    assert entry.feed_names == ["x", "y"] and feed[0].shape == (16, 8)
    assert len(ro) == len(entry.ro_names)
    assert len(rw) == len(entry.rw_names)


def test_the_process_lists_an_entry_after_its_executor_closed():
    main, startup, loss = _program()
    exe = pt.Executor()
    exe.run(startup)
    exe.run(main, feed=_feed(), fetch_list=[loss])
    exe.close()
    del exe
    gc.collect()
    mine = [e for e in ex.compiled_programs() if e.uid == main.desc.uid]
    assert len(mine) == 1
    table = mine[0].op_table()        # no scope, no executor, no feed
    assert {"adam", "__vjp__.mul"} <= {r.op_type
                                       for r in table.ops.values()}
    assert len(ex.compiled_programs()) <= ex._COMPILED_MAX


def test_aot_compiled_for_goes_through_the_entry(trained, monkeypatch):
    from paddle_tpu.parallel.collective_audit import aot_compiled_for
    exe, main, _loss = trained
    entry = _entry(exe, main)
    calls = []
    real = entry.lower_again
    monkeypatch.setattr(entry, "lower_again",
                        lambda: calls.append(1) or real())
    # the last feed is not read: the entry keeps its abstract values
    exe._last_feed_vals = None
    compiled = aot_compiled_for(exe, main)
    assert calls == [1] and "HloModule" in compiled.as_text()


def test_the_mesh_executor_keeps_the_table_too():
    import jax
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel.executor import ParallelExecutor, ShardingSpec
    from paddle_tpu.parallel.mesh import make_mesh
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    main, startup, loss = _program()
    mesh = make_mesh((2, 2), ("data", "model"), jax.devices()[:4])
    pexe = ParallelExecutor(mesh=mesh, sharding=ShardingSpec(
        {"fc_0.w_0": P(None, "model")}))
    pexe.run(startup)
    pexe.run(main, feed=_feed(), fetch_list=[loss])
    entry = _entry(pexe, main)
    assert entry._op_table is None
    table = entry.op_table()
    assert table.module == "jit_fn"
    roles = collections.Counter(r.role for r in table.ops.values())
    assert roles["backward"] and roles["optimizer"] and roles["forward"]


# -- nothing of one process in the scope -------------------------------------

_SCOPES_OF_A_FRESH_PROCESS = """
import re, sys
import paddle_tpu as pt
from paddle_tpu import layers
for _ in range(int(sys.argv[1])):
    pt.Program()                      # moves the uid counter
sys.path.insert(0, {tests!r})
import test_op_table as t
import jax
main, startup, loss = t._program()
exe = pt.Executor()
exe.run(startup)
exe.run(main, feed=t._feed(), fetch_list=[loss])
entry = t._entry(exe, main)
text = entry.jitted.lower(*entry.avals).as_text(debug_info=True)
names = sorted(set(re.findall(r'"(jit\\(step_fn\\)/[^"]*)"', text)))
print("UID", main.desc.uid)
print("\\n".join(names))
"""


def test_the_scope_is_the_same_in_two_processes():
    """No uid, counter or address reaches the HLO: the persistent
    compile cache would miss on every run."""
    import os
    tests = os.path.dirname(os.path.abspath(__file__))
    outs = []
    for bump in ("0", "7"):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.path.dirname(tests))
        done = subprocess.run(
            [sys.executable, "-c",
             _SCOPES_OF_A_FRESH_PROCESS.format(tests=tests), bump],
            capture_output=True, text=True, env=env, timeout=300)
        assert done.returncode == 0, done.stderr[-2000:]
        outs.append(done.stdout.splitlines())
    (uid_a, *names_a), (uid_b, *names_b) = outs
    assert uid_a != uid_b                 # the programs' uids differ
    assert names_a == names_b and len(names_a) > 20
    assert any(re.search(r"/adam/b0\.\d+/", n) for n in names_a)


# -- parsing a module's text ---------------------------------------------------

_MODULE = """HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step_fn)/matmul/b0.3/mul"}
  ROOT %add.1 = f32[8]{0} add(%mul.1, %p0), metadata={op_name="jit(step_fn)/elementwise_add/b0.4/add"}
}

%fused_computation.2 (p0: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%p0.1), metadata={op_name="jit(step_fn)/__vjp__.relu/b0.12/transpose(relu)/b0.2/jvp()/neg"}
}

%body (c: (s32[], f32[8])) -> (s32[], f32[8]) {
  %c = (s32[], f32[8]{0}) parameter(0)
  ROOT %tanh.7 = f32[8]{0:T(8,128)(2,1)} tanh(%c), metadata={op_name="jit(step_fn)/while/b0.5/while/body/tanh/b0.1.2/tanh"}
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/elementwise_add/b0.4/add"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step_fn)/__vjp__.relu/b0.12/transpose(relu)/b0.2/jvp()/neg"}
  %while.1 = (s32[], f32[8]{0}) while(%fusion.2), condition=%cond, body=%body, metadata={op_name="jit(step_fn)/while/b0.5/while"}
  %copy.3 = f32[8]{0} copy(%fusion.2)
  %merged.1 = f32[8]{0} add(%x, %x), metadata={op_name="rw_state['w'];jit(step_fn)/adam/b0.20/add"}
  %call.1 = (bf16[8]{0:T(8,128)(2,1)}, f32[]) custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/__vjp__.scaled_dot_product_attention/b0.30/transpose(jvp(flash_bwd))/pallas_call"}
  ROOT %copy-done.1 = f32[8]{0} copy-done(%copy.3)
}
"""


@pytest.mark.parametrize("name, ref", [
    ("fusion.1", OpRef("elementwise_add", "forward", (0,), 4)),
    ("mul.1", OpRef("matmul", "forward", (0,), 3)),
    # a grad op that applies its forward op's pullback: the FIRST scope
    ("fusion.2", OpRef("__vjp__.relu", "backward", (0,), 12)),
    # a forward op with a sub-block: the op of the sub-block, the LAST
    ("tanh.7", OpRef("tanh", "forward", (0, 1), 2)),
    ("while.1", OpRef("while", "forward", (0,), 5)),
    # names XLA merged: the first that names a program op
    ("merged.1", OpRef("adam", "optimizer", (0,), 20)),
    ("call.1", OpRef("__vjp__.scaled_dot_product_attention", "backward",
                     (0,), 30)),
])
def test_parse_maps_an_instruction(name, ref):
    assert op_table.parse(_MODULE).ops[name] == ref


# what the step of a looped stack carries (models/looped_lm.py, compiled
# for a described v5e): the loop's forward under jax.vjp, then its grad
# op's transpose of the scan, whose body holds the sub-block ops' rules
_LOOP_MODULE = """HloModule jit_step_fn, is_scheduled=true

%fwd_body (c: (s32[], f32[8])) -> (s32[], f32[8]) {
  %c = (s32[], f32[8]{0}) parameter(0)
  ROOT %dot.3 = f32[8]{0} dot(%c, %c), metadata={op_name="jit(step_fn)/static_rnn/b0.12/jvp()/while/body/closed_call/mul/b0.1.7/dot_general"}
}

%bwd_body (c: (s32[], f32[8])) -> (s32[], f32[8]) {
  %c.1 = (s32[], f32[8]{0}) parameter(0)
  %dot.9 = f32[8]{0} dot(%c.1, %c.1), metadata={op_name="jit(step_fn)/__vjp__.static_rnn/b0.98/transpose(jvp())/while/body/closed_call/mul/b0.1.7/dot_general"}
  %call.4 = f32[8]{0} custom-call(%dot.9), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/__vjp__.static_rnn/b0.98/transpose(jvp())/while/body/closed_call/scaled_dot_product_attention/b0.1.9/flash_bwd_dkv_dq/pallas_call"}
  %multiply.6 = f32[8]{0} multiply(%call.4, %call.4), metadata={op_name="jit(step_fn)/__vjp__.static_rnn/b0.98/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/rms_norm/b0.1.3/mul"}
  %multiply.7 = f32[8]{0} multiply(%multiply.6, %call.4), metadata={op_name="jit(step_fn)/__vjp__.static_rnn/b0.98/transpose(jvp())/while/body/closed_call/checkpoint/rms_norm/b0.1.3/mul"}
  ROOT %dynamic-slice.5 = f32[8]{0} dynamic-slice(%multiply.7), metadata={op_name="jit(step_fn)/__vjp__.static_rnn/b0.98/transpose(jvp())/while/body/dynamic_slice"}
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %while.14 = (s32[], f32[8]{0}) while(%x), condition=%cond, body=%fwd_body, metadata={op_name="jit(step_fn)/static_rnn/b0.12/jvp()/while"}
  %while.15 = (s32[], f32[8]{0}) while(%while.14), condition=%cond, body=%bwd_body, metadata={op_name="jit(step_fn)/__vjp__.static_rnn/b0.98/transpose(jvp())/while"}
  ROOT %neg.2 = f32[8]{0} negate(%x), metadata={op_name="jit(step_fn)/__vjp__.relu/b0.99/transpose(relu)/b0.2/jvp()/neg"}
}
"""


@pytest.mark.parametrize("name, ref", [
    # the loop's forward: the sub-block op, as any forward op with a
    # sub-block hands on
    ("dot.3", OpRef("mul", "forward", (0, 1), 7)),
    ("while.14", OpRef("static_rnn", "forward", (0,), 12)),
    # the loop's GRAD op hands on too: the sub-block op whose rule the
    # transpose ran, as that op's grad, at the sub-block's path
    ("dot.9", OpRef("__vjp__.mul", "backward", (0, 1), 7)),
    ("call.4", OpRef("__vjp__.scaled_dot_product_attention", "backward",
                     (0, 1), 9)),
    # the body under jax.checkpoint (op_names as the ouro-2p6b step's
    # compiled module has them): a norm the transpose computes AGAIN
    # from what the scan kept, and its backward proper, are both the
    # norm's grad rows
    ("multiply.6", OpRef("__vjp__.rms_norm", "backward", (0, 1), 3)),
    ("multiply.7", OpRef("__vjp__.rms_norm", "backward", (0, 1), 3)),
    # what the transpose emits outside any body op stays on the grad op
    ("dynamic-slice.5", OpRef("__vjp__.static_rnn", "backward", (0,), 98)),
    ("while.15", OpRef("__vjp__.static_rnn", "backward", (0,), 98)),
    # a grad op in ONE block with its forward op: the FIRST scope still
    ("neg.2", OpRef("__vjp__.relu", "backward", (0,), 99)),
])
def test_a_loops_grad_op_hands_on_to_its_sub_blocks_ops(name, ref):
    # the program calls the sub-block's op 7 a forward op: the role of
    # what the GRAD op handed on is backward all the same
    known = {(1, 7): ("forward", "mul"), (0, 12): ("forward", "static_rnn"),
             (0, 98): ("backward", "__vjp__.static_rnn")}
    assert op_table.parse(_LOOP_MODULE, known).ops[name] == ref
    assert op_table.parse(_LOOP_MODULE).ops[name] == ref


def test_a_program_without_a_sub_block_parses_to_the_table_it_had():
    """Pinned on the module above as the parent parsed it: no program
    of the benchmark's other cells has a sub-block, so no op_name of
    theirs deepens and none changes hands."""
    table = op_table.parse(_MODULE)
    assert {n: tuple(r) for n, r in table.ops.items()} == {
        "mul.1": ("matmul", "forward", (0,), 3),
        "add.1": ("elementwise_add", "forward", (0,), 4),
        "neg.1": ("__vjp__.relu", "backward", (0,), 12),
        "tanh.7": ("tanh", "forward", (0, 1), 2),
        "fusion.1": ("elementwise_add", "forward", (0,), 4),
        "fusion.2": ("__vjp__.relu", "backward", (0,), 12),
        "while.1": ("while", "forward", (0,), 5),
        "merged.1": ("adam", "optimizer", (0,), 20),
        "call.1": ("__vjp__.scaled_dot_product_attention", "backward",
                   (0,), 30)}
    assert set(table.unmapped) == {"p0", "p0.1", "c", "x", "copy.3",
                                   "copy-done.1"}


def test_parse_keeps_what_has_no_op_and_the_mixed_fusions():
    table = op_table.parse(_MODULE)
    assert table.module == "jit_step_fn"
    assert table.unmapped["copy.3"] == "copy"
    assert table.unmapped["copy-done.1"] == "copy-done"
    assert table.unmapped["x"] == "parameter"
    assert table.mixed == {"fusion.1"}      # matmul b0.3 AND add b0.4


def test_parse_takes_the_role_other_from_the_program():
    known = {(0, 4): ("other", "elementwise_add")}
    table = op_table.parse(_MODULE, known)
    assert table.ops["fusion.1"].role == "other"
    assert table.ops["mul.1"].role == "forward"


def test_a_module_from_an_older_trees_cache_entry_maps_by_type():
    """The persistent compile cache's key leaves metadata out: a hit may
    hand back a module compiled before the op scope existed."""
    stale = re.sub(r"/b0(\.\d+)+/", "/", _MODULE)
    assert "/b0." not in stale
    known = {(0, 3): ("forward", "matmul"), (0, 20): ("optimizer", "adam"),
             (0, 12): ("backward", "__vjp__.relu")}
    table = op_table.parse(stale, known)
    assert table.ops["mul.1"] == OpRef("matmul", "forward", (), -1)
    assert table.ops["merged.1"] == OpRef("adam", "optimizer", (), -1)
    assert table.ops["fusion.2"] == OpRef("__vjp__.relu", "backward",
                                          (), -1)
    # a first scope that is no type of the program is no op
    assert "tanh.7" in table.unmapped and "fusion.1" in table.unmapped
    assert op_table.parse(stale).ops == {}
    # and the reduction carries such rows
    stub = _stub("jit_step_fn", table.ops)
    ops = [[_hlo("mul.1"), 0.0, 10e3], [_hlo("fusion.2"), 10e3, 30e3]]
    report = profiler.op_times({"planes": [_plane(ops)]}, [stub])[0]
    assert [(r["op_type"], r["op_index"], r["flops"])
            for r in report["rows"]] == [("__vjp__.relu", -1, None),
                                         ("matmul", -1, None)]
    assert "__vjp__.relu\n" in profiler.op_time_table(report, by="op") \
        + "\n"


def test_scope_names_block_path_and_index():
    assert op_table.scope((0,), 412) == "b0.412"
    assert op_table.scope((0, 2), 7) == "b0.2.7"


# -- the reduction of a trace --------------------------------------------------

def _stub(module, ops, unmapped=(), mixed=(), cost=(), kinds=None, uid=None):
    table = OpTable(module, dict(ops), {n: "copy" for n in unmapped},
                    frozenset(mixed))
    return types.SimpleNamespace(
        op_table=lambda: table, uid=uid,
        cost=types.SimpleNamespace(ops=[
            types.SimpleNamespace(block_path=(0,), op_index=i, flops=f,
                                  bytes_accessed=b) for i, f, b in cost]),
        program_ops=lambda: kinds or {})


MATMUL = OpRef("matmul", "forward", (0,), 3)
MATMUL_GRAD = OpRef("__vjp__.matmul", "backward", (0,), 9)
ADAM = OpRef("adam", "optimizer", (0,), 20)
SOFTMAX = OpRef("softmax", "forward", (0,), 1)
BODY = OpRef("tanh", "forward", (0, 1), 2)
WHILE = OpRef("while", "forward", (0,), 5)

TRAIN = _stub("jit_step_fn",
              {"fusion.1": MATMUL, "fusion.2": MATMUL_GRAD,
               "fusion.3": ADAM, "while.1": WHILE, "tanh.7": BODY},
              unmapped=["copy.3"], mixed=["fusion.2"],
              cost=[(3, 4000, 100), (9, 8000, 200), (20, 12, 1600000)],
              kinds={(0, 3): ("forward", "matmul"),
                     (0, 9): ("backward", "__vjp__.matmul"),
                     (0, 20): ("optimizer", "adam")}, uid=41)
# another module whose `fusion.1` is another op
DECODE = _stub("jit_step_fn", {"fusion.1": SOFTMAX, "fusion.9": SOFTMAX})


def _plane(ops, modules=None, device=0):
    lines = [{"name": "XLA Ops", "events": ops}]
    if modules is not None:
        lines.append({"name": "XLA Modules", "events": modules})
    return {"name": f"/device:TPU:{device}", "lines": lines}


def _hlo(name, opcode="fusion"):
    return f"%{name} = f32[8]{{0:T(8,128)}} {opcode}(f32[8]{{0}} %x)"


TRAIN_RUN = [  # one run of the train module, from t: 100 us in all
    lambda t: [_hlo("fusion.1"), t, 10e3],
    lambda t: [_hlo("while.1", "while"), t + 10e3, 40e3],
    lambda t: [_hlo("tanh.7", "tanh"), t + 12e3, 15e3],     # in the body
    lambda t: [_hlo("tanh.7", "tanh"), t + 30e3, 15e3],
    lambda t: [_hlo("fusion.2"), t + 50e3, 25e3],
    lambda t: [_hlo("copy.3", "copy"), t + 75e3, 5e3],
    lambda t: [_hlo("fusion.3"), t + 80e3, 20e3],
]


def _rows(report):
    return {(r["role"], r["op_type"], r["block_path"], r["op_index"]):
            r for r in report["rows"]}


def test_seconds_by_row_with_a_while_body_and_an_unmapped_copy():
    ops = [make(t) for t in (0.0, 200e3) for make in TRAIN_RUN]
    modules = [["jit_step_fn(123)", 0.0, 100e3],
               ["jit_step_fn(123)", 200e3, 100e3]]
    trace = {"planes": [_plane(ops, modules), {"name": "/host:CPU",
                                               "lines": []}]}
    report = profiler.op_times(trace, [DECODE, TRAIN])[0]
    rows = _rows(report)
    us = {k[1]: r["seconds"] * 1e6 for k, r in rows.items()}
    # the while keeps its own 10 us a run, its body's 30 go to the body
    assert us == pytest.approx({"matmul": 20, "while": 20, "tanh": 60,
                                "__vjp__.matmul": 50, "adam": 40})
    assert rows[("forward", "tanh", (0, 1), 2)]["calls"] == 4
    assert report["unmapped_s"] == pytest.approx(10e-6)
    assert report["unmapped_top"] == [["copy.3", pytest.approx(10e-6)]]
    assert report["busy_s"] == pytest.approx(200e-6)
    assert sum(r["seconds"] for r in report["rows"]) \
        + report["unmapped_s"] == pytest.approx(report["busy_s"])
    assert report["mixed_fusion_s"] == pytest.approx(50e-6)
    # the static counts of the same op, for one run, and the runs
    grad = rows[("backward", "__vjp__.matmul", (0,), 9)]
    assert (grad["flops"], grad["bytes_accessed"]) == (8000, 200)
    (listed,) = report["programs"]
    assert listed["program"] == 1 and listed["runs"] == 2
    assert listed["uid"] == 41
    assert listed["static_by_type"]["optimizer"]["adam"] == [12, 1600000]
    assert "ambiguous" not in {r["role"] for r in report["rows"]}


def test_colliding_names_go_to_the_module_whose_table_holds_the_run():
    """Both tables hold `fusion.1`; the run also shows `fusion.9`, which
    only the decode table holds: the whole run is the decode module's."""
    ops = [[_hlo("fusion.1"), 0.0, 10e3], [_hlo("fusion.9"), 10e3, 10e3]]
    for modules in (None, [["jit_step_fn(7)", 0.0, 20e3]]):
        report = profiler.op_times({"planes": [_plane(ops, modules)]},
                                   [TRAIN, DECODE])[0]
        assert [(r["op_type"], r["seconds"]) for r in report["rows"]] == \
            [("softmax", pytest.approx(20e-6))]


def test_ambiguous_where_it_cannot_be_decided():
    """A window without the modules line that ran both programs: no
    table holds every name, `fusion.1` is matmul in one and softmax in
    the other, and nothing says which ran."""
    ops = [[_hlo("fusion.1"), 0.0, 10e3], [_hlo("fusion.9"), 10e3, 10e3],
           [_hlo("fusion.3"), 20e3, 30e3], [_hlo("nobody.1"), 50e3, 5e3]]
    report = profiler.op_times({"planes": [_plane(ops)]},
                               [TRAIN, DECODE])[0]
    rows = _rows(report)
    assert rows[("ambiguous", "", (), -1)]["seconds"] == \
        pytest.approx(10e-6)
    assert rows[("forward", "softmax", (0,), 1)]["seconds"] == \
        pytest.approx(10e-6)
    assert rows[("optimizer", "adam", (0,), 20)]["seconds"] == \
        pytest.approx(30e-6)
    assert report["unmapped_top"] == [["nobody.1", pytest.approx(5e-6)]]
    # with the modules line each run is decided
    modules = [["jit_step_fn(1)", 0.0, 20e3], ["jit_step_fn(2)", 20e3, 40e3]]
    report = profiler.op_times({"planes": [_plane(ops, modules)]},
                               [TRAIN, DECODE])[0]
    assert "ambiguous" not in {r["role"] for r in report["rows"]}


def test_a_module_of_another_name_maps_to_nothing():
    ops = [[_hlo("fusion.1"), 0.0, 10e3]]
    report = profiler.op_times(
        {"planes": [_plane(ops, [["jit_add(5)", 0.0, 10e3]])]}, [TRAIN])[0]
    assert report["rows"] == [] and \
        report["unmapped_s"] == pytest.approx(10e-6)


def test_each_device_has_its_own_report():
    ops = [make(0.0) for make in TRAIN_RUN]
    trace = {"planes": [_plane(ops, device=0), _plane(ops[:1], device=3)]}
    reports = profiler.op_times(trace, [TRAIN])
    assert sorted(reports) == [0, 3]
    assert reports[3]["busy_s"] == pytest.approx(10e-6)


@pytest.mark.parametrize("event, name", [
    ("%fusion.717 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop",
     "fusion.717"),
    ("fusion.717 fusion", "fusion.717"),
    ("%copy-start.8 = (f32[2]{0}, u32[]) copy-start(f32[2]{0} %w)",
     "copy-start.8"),
    ("jvp_flash_fwd_.1 custom-call:tpu_custom_call", "jvp_flash_fwd_.1"),
])
def test_instruction_name_of_an_event(event, name):
    assert profiler.instruction_name(event) == name


@pytest.mark.parametrize("by, first", [
    ("type", "tanh"), ("op", "tanh b0/1:op2")])
def test_the_table_is_rendered_heaviest_first(by, first):
    ops = [make(t) for t in (0.0, 200e3) for make in TRAIN_RUN]
    report = profiler.op_times({"planes": [_plane(ops)]}, [TRAIN])[0]
    text = profiler.op_time_table(report, by=by)
    lines = text.splitlines()
    assert "no program op 5.0 %" in lines[0]
    assert "in fusions over several ops 25.0 %" in lines[0]
    assert lines[2].endswith(first), text
    adam = next(ln for ln in lines if " adam" in ln).split()
    # 20 us a run, 20 % of busy, 2 calls, 12 FLOP and 1.6 MB a run
    assert adam[:3] == ["0.020", "20.00", "2"]
    assert adam[4] == "1.6" and float(adam[6]) == pytest.approx(80.0)
    assert lines[-1].endswith("copy.3")
    by_calls = profiler.op_time_table(report, by=by, sorted_key="calls")
    assert by_calls.splitlines()[2].endswith(first)      # 4 calls


def test_cost_table_gains_the_measured_columns(trained):
    exe, main, _loss = trained
    entry = _entry(exe, main)
    table = entry.op_table()
    name, ref = next((n, r) for n, r in table.ops.items()
                     if r.op_type == "adam")
    ops = [[_hlo(name), 0.0, 10e3], [_hlo(name), 50e3, 10e3]]
    report = profiler.op_times({"planes": [_plane(ops)]}, [entry])[0]
    text = exe.cost_table(main, measured=report)
    assert f"adam b0:op{ref.op_index}" in text and "100.00" in text
    (row,) = report["rows"]
    assert row["flops"] > 0 and row["bytes_accessed"] > 0
    assert exe.cost_table(pt.Program(), measured=report).count("\n") == 1
    assert "mul" in exe.cost_table(main)          # the static table stays


# -- the rest of the profiler --------------------------------------------------

def _gc_spans(heard):
    return [e for e in heard if e["name"] == "runtime::gc"]


def test_a_full_collection_is_one_span_on_the_collecting_thread():
    heard = []
    profiler.add_event_listener(heard.append)
    try:
        gc.collect(0)
        gc.collect(1)
        with profiler.RecordEvent("after the young ones"):
            pass
        assert _gc_spans(heard) == []
        worker = threading.Thread(target=gc.collect, name="collector")
        worker.start()
        worker.join(30)
        # the hook runs no listener: the span waits for the next one
        assert _gc_spans(heard) == []
        with profiler.RecordEvent("after the full one"):
            pass
    finally:
        profiler.remove_event_listener(heard.append)
    (span,) = _gc_spans(heard)
    assert span["cat"] == profiler.CAT_RUNTIME and span["dur"] > 0
    assert span["tid"] == worker.ident != threading.get_ident()
    assert "collected" in span["args"]
    assert heard.index(span) < [e["name"] for e in heard].index(
        "after the full one")
    assert gc.callbacks.count(profiler._on_gc) == 1


def test_a_collection_inside_a_listeners_lock_does_not_deadlock():
    """A collection starts inside whatever allocation set it off: here
    inside a listener that holds its own, not reentrant, lock."""
    lock, heard = threading.Lock(), []

    def listener(ev):
        with lock:
            heard.append(ev["name"])
            if ev["name"] == "outer":
                gc.collect()

    done = threading.Event()

    def work():
        with profiler.RecordEvent("outer"):
            pass
        with profiler.RecordEvent("next"):
            pass
        done.set()

    profiler.add_event_listener(listener)
    try:
        worker = threading.Thread(target=work, daemon=True)
        worker.start()
        assert done.wait(30), "the hook called a listener from inside " \
            "the collection"
    finally:
        profiler.remove_event_listener(listener)
    assert heard == ["outer", "runtime::gc", "next"]


@pytest.mark.parametrize("key, first", [
    ("calls", "often"), ("total", "long"), ("max", "long"),
    ("ave", "long"), ("min", "long"), (None, "often")])
def test_summary_is_ordered_by_sorted_key(key, first):
    profiler.start_profiler()
    for _ in range(3):
        profiler.emit("often", 1.0, 0.001)
    profiler.emit("long", 2.0, 0.5)
    got = profiler.stop_profiler(sorted_key=key)
    assert list(got)[0] == first
    assert got["often"]["calls"] == 3
    assert got["often"]["ave_us"] == pytest.approx(1000.0)
    assert got["long"]["max_us"] == got["long"]["min_us"] == \
        pytest.approx(0.5e6)


def test_summary_refuses_an_unknown_sorted_key():
    with pytest.raises(ValueError):
        profiler.summary(sorted_key="median")
