"""The five readers of the token server's pass (chipbench/engine_pass.py
and the layer_metrics that use it), each on a hand-filled run: a
Collector given spans and a stats dict. On a rehearsal (``reduced``
None), on a train run and on a program that lacks the span or the
``loop`` entry (the parent of the PR that added them) every one returns
None and does not raise."""
import os

import pytest

import chipbench
from chipbench.manifest import Manifest
from chipbench.spans import Collector, Span

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    chipbench.__file__)))
MANIFEST = Manifest(REPO)
ON_CHIP = object()                    # a device plane was there
WINDOW = (100.0, 104.0)
SERVE_CELLS = ["decoder-lm-base.serve-chat",
               "granite-4p0-h-micro.serve-sessions",
               "zaya1-8b.serve-reasoning"]

#: metric -> (unit, source)
NEW = {
    "engine_collect_ms.serve": ("ms", "program_span"),
    "engine_idle_wait_share_pct.serve": ("%", "program_span"),
    "engine_cpu_ms.serve": ("ms", "program_counter"),
    "engine_off_cpu_ms.serve": ("ms", "program_counter"),
    "engine_stall_ms_max.serve": ("ms", "program_span"),
}
SPAN_READERS = [m for m, (_u, src) in NEW.items() if src == "program_span"]
COUNTER_READERS = [m for m in NEW if m not in SPAN_READERS]

# three passes in the window: an idle stretch, then collect + iteration
# three times; a pass before the window and one that ends after it
PASS_SPANS = [
    ("generation::collect", 99.0, 0.5), ("generation::iteration", 99.5, 0.4),
    ("generation::idle_wait", 99.9, 0.3),             # ends at 100.2
    ("generation::collect", 100.2, 0.002),
    ("generation::iteration", 100.202, 0.010),
    ("generation::collect", 100.212, 0.001),
    ("generation::iteration", 100.213, 0.020),
    ("generation::idle_wait", 100.233, 0.5),
    ("generation::collect", 100.733, 0.003),
    ("generation::iteration", 100.736, 0.030),
    ("generation::stall", 100.212, 0.021),
    ("generation::stall", 100.733, 0.090),
    ("generation::collect", 103.9, 0.05),             # ends in the window
    ("generation::iteration", 103.95, 0.2),           # ends after it
    ("generation::stall", 103.9, 0.25),               # and so does this
]
LOOP = {"passes": 400, "wall_seconds": 2.0, "cpu_seconds": 1.5,
        "device_wait_seconds": 6.0, "voluntary_switches": 900,
        "involuntary_switches": 3}


def _collector(spans):
    c = Collector()
    c.spans = [Span(name, start, dur, start + dur)
               for name, start, dur in spans]
    return c


def _read(metric, run):
    return MANIFEST.load_reader(metric).read(run)


def _run(kind="serve", spans=PASS_SPANS, reduced=ON_CHIP, loop=LOOP):
    stats = {"steps": 400, "prefills": 40}
    if loop is not None:
        stats["loop"] = loop
    return dict(kind=kind, spans=_collector(spans), window=WINDOW,
                reduced=reduced, engine_stats=stats)


@pytest.mark.parametrize("metric, value", [
    # collects that ended in the window (2 + 1 + 3 + 50 ms) over the
    # three iterations that did
    ("engine_collect_ms.serve", (0.002 + 0.001 + 0.003 + 0.05) / 3 * 1e3),
    # both idle stretches ended in the window: 0.8 s of 4 s
    ("engine_idle_wait_share_pct.serve", (0.3 + 0.5) / 4.0 * 100.0),
    ("engine_cpu_ms.serve", 1.5 / 400 * 1e3),
    ("engine_off_cpu_ms.serve", (2.0 - 1.5) / 400 * 1e3),
    # the longest that ENDED in the window
    ("engine_stall_ms_max.serve", 90.0),
])
def test_the_arithmetic_on_a_hand_made_run(metric, value):
    assert _read(metric, _run()) == pytest.approx(value)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_none_on_a_rehearsal_and_on_a_train_run(metric):
    assert _read(metric, _run(reduced=None)) is None
    assert _read(metric, _run(kind="train")) is None
    # a train driver's run has no engine_stats and no kind key at all
    bare = dict(spans=_collector(PASS_SPANS), window=WINDOW,
                reduced=ON_CHIP)
    assert _read(metric, bare) is None


PARENT_SPANS = [s for s in PASS_SPANS
                if s[0] == "generation::iteration"] + \
    [("pipeline::fetch_sync", 100.203, 0.008)]


@pytest.mark.parametrize("metric", sorted(NEW))
def test_none_on_a_program_without_the_span_or_the_loop_entry(metric):
    """The parent: iterations and the executor's spans, no collect, no
    idle_wait, no stall, and a stats dict without ``loop``."""
    assert _read(metric, _run(spans=PARENT_SPANS, loop=None)) is None


@pytest.mark.parametrize("metric", COUNTER_READERS)
def test_the_counter_readers_need_a_pass(metric):
    empty = dict(LOOP, passes=0, wall_seconds=0.0, cpu_seconds=0.0)
    assert _read(metric, _run(loop=empty)) is None
    # and read no span: the loop entry alone is enough
    assert _read(metric, _run(spans=[])) is not None


def test_a_program_with_the_spans_that_never_idled_or_stalled_reads_0():
    busy = [s for s in PASS_SPANS if s[0] in ("generation::collect",
                                              "generation::iteration")]
    assert _read("engine_idle_wait_share_pct.serve", _run(spans=busy)) == 0.0
    assert _read("engine_stall_ms_max.serve", _run(spans=busy)) == 0.0


def test_off_cpu_and_cpu_add_up_to_the_pass_less_its_device_wait():
    run = _run()
    assert _read("engine_cpu_ms.serve", run) + \
        _read("engine_off_cpu_ms.serve", run) == pytest.approx(
            LOOP["wall_seconds"] / LOOP["passes"] * 1e3)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_manifest_entry_by_name(metric):
    entries = [m for m in MANIFEST.data["per_layer"]
               if m["name"] == metric]
    assert len(entries) == 1
    entry = entries[0]
    unit, source = NEW[metric]
    assert entry == {"name": metric, "unit": unit, "better": "lower",
                     "source": source, "layer": "Token server",
                     "moves": "tpot_ms_p95", "workloads": SERVE_CELLS}
    # every listed cell reports the end-to-end metric it moves
    moved = next(m for m in MANIFEST.data["end_to_end"]
                 if m["name"] == "tpot_ms_p95")
    assert set(SERVE_CELLS) <= set(moved.get("workloads", SERVE_CELLS))
    for cell in SERVE_CELLS:
        assert metric in [m["name"] for m in
                          MANIFEST.metrics_for(cell, "per_layer")]
    assert MANIFEST.load_reader(metric).__doc__.strip()


def test_the_five_are_appended_and_the_manifest_keeps_its_rules():
    names = [m["name"] for m in MANIFEST.data["per_layer"]]
    assert names[-len(NEW):] == list(NEW)
    assert MANIFEST.problems() == []
