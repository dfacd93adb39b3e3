"""BENCHMARK.json and the files it names: loading, look-up by name,
and the rules a manifest has to keep (checked by tests/chipbench and
before every run, so a broken entry fails before any chip time)."""
from __future__ import annotations

import importlib.util
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


class ManifestError(ValueError):
    pass


class Manifest:
    """The parsed BENCHMARK.json plus the directory the harness's data
    files live in (``root/<bench_dir>``)."""

    def __init__(self, root: str, bench_dir: str = "chipbench"):
        self.root = os.path.abspath(root)
        self.bench = os.path.join(self.root, bench_dir)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.data = json.load(f)

    # -- look-up -----------------------------------------------------
    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(
            f"no workload {name!r} in BENCHMARK.json (have: "
            f"{[w['name'] for w in self.data['workloads']]})")

    def config_entry(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return c
        raise ManifestError(f"no config {name!r} in BENCHMARK.json")

    def load_config(self, name: str) -> dict:
        with open(os.path.join(self.root,
                               self.config_entry(name)["file"])) as f:
            return json.load(f)

    def load_workload(self, name: str) -> dict:
        path = os.path.join(self.bench, "workloads", name + ".json")
        with open(path) as f:
            return json.load(f)

    def metrics_for(self, cell: str, group: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries a cell reports."""
        return [m for m in self.data[group]
                if "workloads" not in m or cell in m["workloads"]]

    def load_reader(self, metric: str):
        """The module ``layer_metrics/<metric>.py``: a docstring and
        ``read(run)``."""
        path = os.path.join(self.bench, "layer_metrics", metric + ".py")
        if not os.path.exists(path):
            raise ManifestError(f"per-layer metric {metric!r} has no "
                                f"reader at {path}")
        spec = importlib.util.spec_from_file_location(
            "chipbench_layer_metric_" + re.sub(r"\W", "_", metric), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    # -- rules -------------------------------------------------------
    def problems(self) -> list:
        """Every broken rule, as text; [] for a sound manifest."""
        d, out = self.data, []
        if set(d) != KEYS:
            out.append(f"top-level keys {sorted(d)} != {sorted(KEYS)}")
            return out
        if not (isinstance(d["run_seconds"], int)
                and 1 <= d["run_seconds"] <= 51):
            out.append("run_seconds must be a whole number 1..51")

        def unique(items, what):
            names = [i.get("name") for i in items]
            for n in names:
                if not isinstance(n, str) or not NAME_RE.match(n):
                    out.append(f"{what} name {n!r} is not a name")
            if len(set(names)) != len(names):
                out.append(f"duplicate {what} names")
            return names

        cfg_names = unique(d["configs"], "config")
        cell_names = unique(d["workloads"], "workload")
        unique(d["end_to_end"] + d["per_layer"], "metric")
        e2e = {m["name"]: m for m in d["end_to_end"]}
        if "setup_s" not in e2e:
            out.append("end_to_end lacks setup_s")
        for c in d["configs"]:
            if set(c) != {"name", "source", "file", "reduced", "why"}:
                out.append(f"config {c.get('name')}: keys {sorted(c)}")
            if not any(c["file"].startswith(p + "/") for p in d["paths"]):
                out.append(f"config file {c['file']} not under paths")
            elif not os.path.exists(os.path.join(self.root, c["file"])):
                out.append(f"config file {c['file']} missing")
            if c["name"] not in {w["config"] for w in d["workloads"]}:
                out.append(f"config {c['name']} is used by no cell")
        pairs = set()
        for w in d["workloads"]:
            if set(w) != {"name", "config", "traffic", "chips", "why"}:
                out.append(f"workload {w.get('name')}: keys {sorted(w)}")
                continue
            if w["config"] not in cfg_names:
                out.append(f"{w['name']}: unknown config {w['config']}")
            if w["chips"] not in (1, 4):
                out.append(f"{w['name']}: chips must be 1 or 4")
            if not NAME_RE.match(w["traffic"]):
                out.append(f"{w['name']}: traffic is not a name")
            if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
                out.append(f"{w['name']}: why must be 1..200 chars, "
                           "one line")
            if (w["config"], w["traffic"]) in pairs:
                out.append(f"{w['name']}: config/traffic pair repeats")
            pairs.add((w["config"], w["traffic"]))
            if not os.path.exists(os.path.join(
                    self.bench, "workloads", w["name"] + ".json")):
                out.append(f"{w['name']}: no workloads/<name>.json")
        four = sum(1 for w in d["workloads"] if w.get("chips") == 4)
        if four > max(1, len(d["workloads"]) // 4):
            out.append(f"{four} four-chip cells of {len(d['workloads'])}"
                       ": at most a quarter (one always may)")
        for m in d["end_to_end"] + d["per_layer"]:
            n = m.get("name")
            if not UNIT_RE.match(str(m.get("unit", ""))):
                out.append(f"{n}: unit {m.get('unit')!r}")
            if m.get("better") not in ("lower", "higher"):
                out.append(f"{n}: better must be lower or higher")
            if m.get("source") not in SOURCES:
                out.append(f"{n}: source {m.get('source')!r}")
            for c in m.get("workloads", []):
                if c not in cell_names:
                    out.append(f"{n}: lists unknown cell {c}")
        for m in d["end_to_end"]:
            if set(m) - {"workloads"} != {"name", "unit", "better",
                                          "bound", "source"}:
                out.append(f"{m.get('name')}: keys {sorted(m)}")
            if m.get("source") not in ("host_clock", "device_trace"):
                out.append(f"{m.get('name')}: an end-to-end metric is "
                           "host_clock or device_trace")
            if not 0 < m.get("bound", 0) <= 0.1:
                out.append(f"{m.get('name')}: bound must be in (0, 0.1]")
        for m in d["per_layer"]:
            if set(m) - {"workloads"} != {"name", "unit", "better",
                                          "source", "layer", "moves"}:
                out.append(f"{m.get('name')}: keys {sorted(m)}")
                continue
            target = e2e.get(m["moves"])
            if target is None:
                out.append(f"{m['name']}: moves unknown {m['moves']}")
                continue
            mine = set(m.get("workloads", cell_names))
            theirs = set(target.get("workloads", cell_names))
            if not mine <= theirs:
                out.append(f"{m['name']}: moves {m['moves']}, which "
                           f"{sorted(mine - theirs)} do not report")
            if not os.path.exists(os.path.join(
                    self.bench, "layer_metrics", m["name"] + ".py")):
                out.append(f"{m['name']}: no layer_metrics/<name>.py")
        for cell in cell_names:
            e = [m["name"] for m in self.metrics_for(cell, "end_to_end")]
            if "setup_s" not in e or len(e) < 2:
                out.append(f"{cell}: needs setup_s and one more "
                           "end-to-end metric")
            if not self.metrics_for(cell, "per_layer"):
                out.append(f"{cell}: reports no per-layer metric")
        return out

    def validate(self):
        bad = self.problems()
        if bad:
            raise ManifestError("BENCHMARK.json: " + "; ".join(bad))
