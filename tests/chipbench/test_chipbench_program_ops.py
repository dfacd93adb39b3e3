"""The readers of device time by program op (chipbench/program_ops.py and
the four layer_metrics that use it) on the recorded trace
(fixtures/train_s2048_two_steps.json.gz) with a stub op table in place
of the program's: the value, None in a rehearsal, None without a
table, None on a run that is not a train run."""
import collections
import os

import pytest

import chipbench
from chipbench import program_ops, trace
from chipbench.manifest import Manifest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    chipbench.__file__)))
MANIFEST = Manifest(REPO)
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "train_s2048_two_steps.json.gz")
TRAIN_CELLS = {w["name"] for w in MANIFEST.data["workloads"]
               if ".train-" in w["name"]}
READERS = ["op_scope_coverage_pct.train", "backward_time_share_pct.train",
           "optimizer_time_share_pct.train", "loss_time_share_pct.train"]

Ref = collections.namedtuple("Ref", "op_type role block_path op_index")
Table = collections.namedtuple("Table", "module ops unmapped mixed")


def _stub_table(ops):
    """By what the recorded names let one tell: the head's softmax
    fusions are the loss, `transpose_jvp*` kernels the backward flash
    kernel, the other kernels the forward one, `fusion.416` Adam, the
    other fusions a forward matmul; copies and the rest have no op."""
    table = {}
    for name in {n.split(" ", 1)[0] for n, _s, _d in ops}:
        if name in ("divide_subtract_fusion", "subtract_subtract_fusion"):
            table[name] = Ref("__vjp__.softmax_with_cross_entropy",
                              "backward", (0,), 900)
        elif name == "add_reduce_fusion":
            table[name] = Ref("softmax_with_cross_entropy", "forward",
                              (0,), 400)
        elif name.startswith("transpose_jvp"):
            table[name] = Ref("__vjp__.scaled_dot_product_attention",
                              "backward", (0,), 700)
        elif name.startswith(("jvp__", "step_fn")):
            table[name] = Ref("scaled_dot_product_attention", "forward",
                              (0,), 30)
        elif name == "fusion.416":
            table[name] = Ref("adam", "optimizer", (0,), 1200)
        elif "fusion" in name:
            table[name] = Ref("matmul", "forward", (0,), 7)
    return Table("jit_step_fn", table, {}, frozenset())


@pytest.fixture(scope="module")
def reduced():
    return trace.Reduced(trace.load_plain(FIXTURE), 1)


@pytest.fixture
def run(reduced, monkeypatch):
    own = _stub_table(reduced.ops[0])
    # the startup program's table shares names and maps next to nothing
    other = Table("jit_step_fn", {"fusion.416": Ref(
        "fill_constant", "forward", (0,), 1)}, {}, frozenset())
    monkeypatch.setattr(program_ops, "tables", lambda: [other, own])
    return {"kind": "train", "reduced": reduced, "steps": [1, 2],
            "spans": None}


def _read(metric, run):
    return MANIFEST.load_reader(metric).read(run)


def _share(reduced, keep):
    busy = reduced.busy_on(0)
    return sum(d for n, _s, d in reduced.ops[0]
               if keep(n.split(" ", 1)[0])) * 1e-9 / busy * 100.0


def test_shares_on_the_recorded_trace(run, reduced):
    table = _stub_table(reduced.ops[0]).ops
    want = {
        "op_scope_coverage_pct.train": lambda n: n in table,
        "backward_time_share_pct.train":
            lambda n: n in table and table[n].role == "backward",
        "optimizer_time_share_pct.train": lambda n: n == "fusion.416",
        "loss_time_share_pct.train": lambda n: n in (
            "divide_subtract_fusion", "subtract_subtract_fusion",
            "add_reduce_fusion"),
    }
    got = {m: _read(m, run) for m in READERS}
    for metric in READERS:
        assert got[metric] == pytest.approx(_share(reduced, want[metric]))
    # what the recording lets a reader check by hand
    assert 90.0 < got["op_scope_coverage_pct.train"] < 100.0
    assert got["loss_time_share_pct.train"] == pytest.approx(
        (9877788 + 9577177 + 6286930) / reduced.busy_on(0) / 1e9 * 100)
    assert got["backward_time_share_pct.train"] > \
        got["loss_time_share_pct.train"] > \
        got["optimizer_time_share_pct.train"] > 0


def test_the_roles_and_what_maps_to_nothing_sum_to_busy_time(run, reduced):
    by_op = program_ops.seconds_by_op(run)
    assert None in by_op                       # copies and the like
    assert sum(by_op.values()) == pytest.approx(reduced.busy_on(0))
    roles = collections.Counter()
    for ref, seconds in by_op.items():
        roles[ref.role if ref else "none"] += seconds
    for role in ("backward", "optimizer"):
        assert program_ops.role_share_pct(run, role) == pytest.approx(
            roles[role] / reduced.busy_on(0) * 100)


def test_the_table_under_which_most_time_maps_is_taken(run):
    by_op = program_ops.seconds_by_op(run)
    assert "adam" in {r.op_type for r in by_op if r}
    assert "fill_constant" not in {r.op_type for r in by_op if r}


@pytest.mark.parametrize("metric", READERS)
def test_none_in_a_rehearsal_and_on_another_kind_of_run(metric, run):
    assert _read(metric, dict(run, reduced=None)) is None
    assert _read(metric, dict(run, kind="serve")) is None
    assert _read(metric, {"spans": None}) is None   # the manifest's call


@pytest.mark.parametrize("metric", READERS)
def test_none_on_a_program_without_the_table(metric, reduced, monkeypatch):
    monkeypatch.setattr(program_ops, "tables", lambda: [])
    run = {"kind": "train", "reduced": reduced, "steps": [1]}
    assert _read(metric, run) is None
    # and where the program's tables hold none of the names seen
    nothing = Table("jit_step_fn", {"nobody.1": Ref(
        "matmul", "forward", (0,), 1)}, {}, frozenset())
    monkeypatch.setattr(program_ops, "tables", lambda: [nothing])
    assert _read(metric, dict(run)) is None


def test_tables_is_empty_on_a_program_that_lacks_them(monkeypatch):
    import paddle_tpu.core.executor as ex
    monkeypatch.delattr(ex, "compiled_programs")
    assert list(program_ops.tables()) == []


@pytest.mark.parametrize("metric", READERS)
def test_the_manifest_entry(metric):
    (m,) = [x for x in MANIFEST.data["per_layer"] if x["name"] == metric]
    assert set(m) == {"name", "unit", "better", "source", "layer",
                      "moves", "workloads"}
    assert (m["unit"], m["source"], m["layer"], m["moves"]) == (
        "%", "device_trace", "Executor", "train_tokens_per_s")
    assert set(m["workloads"]) == TRAIN_CELLS
    assert m["better"] == ("higher" if "coverage" in metric else "lower")
    assert MANIFEST.data["per_layer"][-4:] == [
        x for x in MANIFEST.data["per_layer"] if x["name"] in READERS]
    assert "None" in MANIFEST.load_reader(metric).__doc__
