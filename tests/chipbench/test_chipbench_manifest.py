"""BENCHMARK.json keeps its rules, and a cell, a configuration and a
per-layer metric are each added by new files plus one entry."""
import json
import os

import pytest

from chipbench.manifest import Manifest, ManifestError

import chipbench

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    chipbench.__file__)))


def _edit(root, fn):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        data = json.load(f)
    fn(data)
    with open(path, "w") as f:
        json.dump(data, f)
    return Manifest(str(root))


def test_committed_manifest_is_sound():
    m = Manifest(REPO)
    assert m.problems() == []
    d = m.data
    assert [x["name"] for x in d["end_to_end"]] == [
        "train_tokens_per_s", "serve_tokens_per_s", "ttft_ms_p95",
        "tpot_ms_p95", "setup_s"]
    assert {c["name"] for c in d["configs"]} == {"transformer-base",
                                                 "decoder-lm-base"}
    assert d["paths"] == ["chipbench", "tests/chipbench"]
    # every file the manifest leans on is found by name
    for w in d["workloads"]:
        wl = m.load_workload(w["name"])
        assert wl["config"] == w["config"] and wl["chips"] == w["chips"]
        assert m.load_config(w["config"])["name"] == w["config"]
        for pm in m.metrics_for(w["name"], "per_layer"):
            mod = m.load_reader(pm["name"])
            # a reader is its docstring and read(run); layer, unit,
            # moves and source are BENCHMARK.json's alone
            assert callable(mod.read) and mod.__doc__
            assert mod.read({"spans": None}) is None
            assert not {"LAYER", "UNIT", "MOVES", "SOURCE"} & set(
                vars(mod))


@pytest.mark.parametrize("breaker, word", [
    (lambda d: d["workloads"][0].update(name="has space"), "not a name"),
    (lambda d: d["end_to_end"][0].update(unit="tokens per second"),
     "unit"),
    (lambda d: d["end_to_end"][0].update(unit="x" * 17), "unit"),
    (lambda d: d["per_layer"][0].update(moves="ttft_ms_p95"),
     "do not report"),
    (lambda d: d["per_layer"][0].update(moves="nothing"), "moves unknown"),
    (lambda d: d["workloads"][0].update(chips=4), "four-chip"),
    (lambda d: d["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda d: d["per_layer"][0].update(why="no such key"), "keys"),
    (lambda d: d["workloads"].append(dict(d["workloads"][0],
                                          name="again")), "pair repeats"),
    (lambda d: d.update(run_seconds=52), "run_seconds"),
    (lambda d: d["end_to_end"].pop(), "setup_s"),
])
def test_rules_catch(bench_copy, breaker, word):
    m = _edit(bench_copy, breaker)
    assert any(word in p for p in m.problems()), m.problems()
    with pytest.raises(ManifestError):
        m.validate()


def test_one_four_chip_cell_in_four_and_no_more(bench_copy):
    def second(d):
        d["workloads"][2]["chips"] = 4
    assert any("four-chip" in p
               for p in _edit(bench_copy, second).problems())


def test_add_cell_config_and_metric_as_files(bench_copy):
    """The data-driven promise: nothing that is there is edited."""
    root = str(bench_copy)
    before = {}
    for dirpath, _dirs, files in os.walk(root):
        for f in files:
            if f != "BENCHMARK.json":
                p = os.path.join(dirpath, f)
                before[p] = open(p, "rb").read()

    bench = os.path.join(root, "chipbench")
    cfg = json.load(open(os.path.join(
        bench, "configs", "decoder-lm-base.json")))
    cfg["name"] = "decoder-lm-dummy"
    json.dump(cfg, open(os.path.join(
        bench, "configs", "decoder-lm-dummy.json"), "w"))
    wl = json.load(open(os.path.join(
        bench, "workloads", "decoder-lm-base.serve-chat.json")))
    wl.update(name="decoder-lm-dummy.serve-burst",
              config="decoder-lm-dummy")
    wl["traffic"]["rate_per_s"] = 5.0
    json.dump(wl, open(os.path.join(
        bench, "workloads", "decoder-lm-dummy.serve-burst.json"), "w"))
    with open(os.path.join(bench, "layer_metrics",
                           "prefills.dummy.py"), "w") as f:
        f.write('"""Prefill spans heard."""\n\n\ndef read(run):\n'
                '    return float(len(run["spans"].named('
                '"generation::prefill[")))\n')

    def add(d):
        d["configs"].append({
            "name": "decoder-lm-dummy", "source": "https://example.org",
            "file": "chipbench/configs/decoder-lm-dummy.json",
            "reduced": [], "why": "a dummy"})
        d["workloads"].append({
            "name": "decoder-lm-dummy.serve-burst",
            "config": "decoder-lm-dummy", "traffic": "serve-burst",
            "chips": 1, "why": "a dummy"})
        for m in d["end_to_end"]:
            if m["name"] in ("serve_tokens_per_s", "ttft_ms_p95",
                             "tpot_ms_p95"):
                m["workloads"].append("decoder-lm-dummy.serve-burst")
        d["per_layer"].append({
            "name": "prefills.dummy", "unit": "count", "better": "higher",
            "source": "program_span", "layer": "Token server",
            "moves": "ttft_ms_p95",
            "workloads": ["decoder-lm-dummy.serve-burst"]})

    m = _edit(root, add)
    assert m.problems() == []
    assert m.load_workload("decoder-lm-dummy.serve-burst")[
        "traffic"]["rate_per_s"] == 5.0
    names = [x["name"] for x in m.metrics_for(
        "decoder-lm-dummy.serve-burst", "per_layer")]
    assert "prefills.dummy" in names and "first_step_other_s" in names

    from chipbench.spans import Collector, Span
    spans = Collector()
    spans.spans += [Span("generation::prefill[7]", 0.0, 0.1, 0.1),
                    Span("generation::decode_step[16]", 0.1, 0.1, 0.2)]
    assert m.load_reader("prefills.dummy").read({"spans": spans}) == 1.0
    for p, content in before.items():
        assert open(p, "rb").read() == content, f"{p} was edited"
