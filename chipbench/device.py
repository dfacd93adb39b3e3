"""The device a run is on: discovery, the table of peaks, memory."""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class DeviceError(RuntimeError):
    """No accelerator, too few chips, or a chip that is not in the
    table of peaks: a run that cannot name its yardstick does not run."""


def load_peaks(path: str | None = None) -> dict:
    with open(path or os.path.join(HERE, "peaks.json")) as f:
        return json.load(f)


def peaks_for(kind: str, table: dict | None = None) -> dict:
    table = load_peaks() if table is None else table
    if kind not in table:
        raise DeviceError(
            f"device_kind {kind!r} is not in chipbench/peaks.json "
            f"(known: {sorted(table)}); add its published peaks with "
            "their source before measuring on it")
    return table[kind]


def describe(devices, chips: int, rehearse: bool) -> dict:
    """{"platform", "kind", "count"} of the chips a cell uses, or a
    DeviceError. A rehearsal may run anywhere EXCEPT on a TPU: a toy
    size must never print a line that names one."""
    if not devices:
        raise DeviceError("JAX reports no devices")
    platform = devices[0].platform
    if rehearse:
        if platform == "tpu":
            raise DeviceError(
                "--rehearse runs toy sizes and refuses a TPU: its line "
                "would carry a device name next to numbers that mean "
                "nothing there")
    elif platform != "tpu":
        raise DeviceError(
            f"JAX found no TPU (platform {platform!r}); the benchmark "
            "does not fall back to another backend")
    if len(devices) < chips:
        raise DeviceError(
            f"the cell asks for {chips} chip(s), JAX reports "
            f"{len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": chips}


def memory_peak_bytes(devices, program_temp_bytes: int = 0) -> int:
    """Peak on the fullest chip. The allocator's ``peak_bytes_in_use``
    misses the temporaries XLA plans inside a program (PERF.md, PR 22:
    1.49 GB read after steps whose program holds 3.8 GB of them), so
    the per-device temporaries of the largest program the window ran
    are added to what was live when it ran, and the larger reading
    wins."""
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        live = int(st.get("bytes_in_use") or 0)
        peak = max(peak, int(st.get("peak_bytes_in_use") or 0),
                   live + int(program_temp_bytes))
    return peak
