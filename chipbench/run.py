#!/usr/bin/env python3
"""The benchmark's one command:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its files under chipbench/ by name,
runs the driver of its kind on the chips it asks for and prints, as the
last line of stdout, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``. Earlier stdout lines are notes for a reader, one JSON
object each. Exits non-zero and prints no result without a TPU, with
fewer chips than the cell asks for, on a chip that is not in
peaks.json, or in a directory without the program.

``--rehearse`` runs the same control flow at the toy sizes the files
carry under ``rehearse``, on anything but a TPU (tests/chipbench).
``--set traffic.rate_per_s=60`` overrides a traffic parameter for a
sweep; the driver's check never passes it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()      # as early as this process can read

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import device as devmod  # noqa: E402
from chipbench import trace as tracemod  # noqa: E402
from chipbench.manifest import Manifest  # noqa: E402
from chipbench.spans import Collector  # noqa: E402


def note(**kw):
    print(json.dumps(kw, default=str), flush=True)


class Context:
    """What a driver gets, and what the per-layer readers read
    (``ctx.run``: a dict the driver fills, plus the keys set here)."""

    def __init__(self, args, cell, config, workload, devices, peaks,
                 workdir):
        self.seed = int(args.seed)
        self.rehearse = bool(args.rehearse)
        self.trace_on = bool(args.trace)
        self.config, self.workload = config, workload
        self.chips = int(cell["chips"])
        self.devices = list(devices[:self.chips])
        self.workdir = workdir
        self.seconds = float(args.seconds)
        if self.trace_on:
            # a traced run measures a short window of its own: traces
            # are large and the tracer slows the host
            self.seconds = min(self.seconds,
                               float(workload.get("trace_seconds", 4)))
        self.spans = Collector()
        self.window = None            # (t0, t1) host perf_counter
        self.setup_s = None
        self._marker_host = None
        self._trace_dir = os.path.join(workdir, "trace")
        self.run = {"spans": self.spans, "config": config,
                    "workload": workload, "peaks": peaks,
                    "chips": self.chips, "reduced": None}
        self.note = note
        self.phases = []              # (what, seconds since process start)

    def phase(self, what: str):
        """A set-up milestone, for the notes: where set-up time goes."""
        self.phases.append((what, time.perf_counter() - T_PROCESS))

    def open_window(self, at=None) -> float:
        """Set-up ends here. Starts the device trace when asked and
        drops the marker that ties the host's clock to the trace's."""
        import jax
        t0 = time.perf_counter() if at is None else at
        if self.trace_on:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self._trace_dir,
                                     profiler_options=opts)
            with jax.profiler.TraceAnnotation(tracemod.MARKER):
                self._marker_host = time.perf_counter()
            t0 = max(t0, self._marker_host)
        self.setup_s = t0 - T_PROCESS
        self.phases.append(("window opens", self.setup_s))
        self.window = (t0, None)
        return t0

    def close_window(self, t1: float):
        self.window = (self.window[0], t1)
        self.run["window"] = self.window
        if self.trace_on:
            import jax
            jax.profiler.stop_trace()

    def reduce_trace(self, keep=None):
        """After the driver returns: the traced window, reduced, with
        the program's spans moved onto the trace's clock."""
        path = tracemod.find_xplane(self._trace_dir)
        plain = tracemod.load_xplane(path)
        if keep:
            os.makedirs(os.path.dirname(keep) or ".", exist_ok=True)
            tracemod.save_plain(plain, keep)
            with open(keep + ".outline.json", "w") as f:
                json.dump(tracemod.outline(path), f, indent=1)
        mark = tracemod.marker_ns(plain)
        if mark is None:
            raise RuntimeError("the window marker is not in the trace")
        shift = mark - self._marker_host * 1e9
        t0, t1 = self.window
        host = [(s.name, s.start * 1e9 + shift, s.end * 1e9 + shift)
                for s in list(self.spans.spans) if s.end >= t0]
        if self.rehearse and not tracemod.device_ids(plain):
            return None          # no TPU plane off the chip: spans only
        self.run["reduced"] = tracemod.Reduced(
            plain, self.chips,
            window_ns=(t0 * 1e9 + shift, t1 * 1e9 + shift),
            host_spans=host)
        return self.run["reduced"]


def _override(workload: dict, items):
    for item in items or []:
        key, _, raw = item.partition("=")
        node = workload
        parts = key.split(".")
        for p in parts[:-1]:
            node = node[p]
        node[parts[-1]] = json.loads(raw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--set", action="append", metavar="KEY=JSON")
    ap.add_argument("--keep-trace", metavar="FILE.json.gz",
                    help="also save the trace's plain form here")
    args = ap.parse_args(argv)

    manifest = Manifest(ROOT)
    manifest.validate()
    cell = manifest.cell(args.workload)
    config = manifest.load_config(cell["config"])
    workload = manifest.load_workload(cell["name"])
    _override(workload, args.set)
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        print("chipbench: the program (paddle_tpu/) is not in this "
              "directory; nothing to measure", file=sys.stderr)
        return 2
    # the configuration's environment, before the program is imported
    for k, v in config.get("env", {}).items():
        os.environ[k] = str(v)

    import jax
    if args.rehearse:
        # toy programs never enter the checkout's compile cache
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        # every program, however quick to compile, is found again by
        # the next run: set-up stays the same from run to run
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devices = jax.devices()
        dev = devmod.describe(devices, int(cell["chips"]), args.rehearse)
        peaks = devmod.load_peaks().get(dev["kind"]) if args.rehearse \
            else devmod.peaks_for(dev["kind"])
    except (devmod.DeviceError, RuntimeError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2

    workdir = tempfile.mkdtemp(prefix="chipbench_")
    ctx = Context(args, cell, config, workload, devices, peaks, workdir)
    try:
        ctx.phase("imports, manifest, devices")
        ctx.spans.install()
        driver = importlib.import_module(
            "chipbench.drivers." + workload["kind"])
        result = driver.run(ctx)
        note(setup_phases_s=[[w, round(t, 3)] for w, t in ctx.phases])
        ctx.run["setup_s"] = ctx.setup_s
        t0, t1 = ctx.window
        ctx.run["compile_backend_s"] = ctx.spans.compile_seconds(None, t0)
        out = {"correct": result["correct"],
               "attempted": result["attempted"],
               "failed": result["failed"], "metrics": {}}
        device = dict(dev, memory_peak_bytes=devmod.memory_peak_bytes(
            ctx.devices, result.get("program_temp_bytes", 0)))
        if args.trace:
            red = ctx.reduce_trace(args.keep_trace)
            if red is not None:
                device.update(busy_s=red.busy_s, window_s=red.window_s)
                out["breakdown"] = red.breakdown()
            for m in manifest.metrics_for(cell["name"], "per_layer"):
                value = manifest.load_reader(m["name"]).read(ctx.run)
                if value is not None:
                    out["metrics"][m["name"]] = {"value": value,
                                                 "unit": m["unit"]}
        else:
            values = dict(result["end_to_end"], setup_s=ctx.setup_s)
            for m in manifest.metrics_for(cell["name"], "end_to_end"):
                out["metrics"][m["name"]] = {"value": values[m["name"]],
                                             "unit": m["unit"]}
        out["device"] = device
        if args.rehearse:
            out["rehearsal"] = True
    finally:
        ctx.spans.uninstall()
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
