"""Live performance attribution: MFU gauges and the step-phase
breakdown, fed by the always-on profiler event listener.

The static cost model (``analysis/cost_model.py``) says how many FLOPs
one step SHOULD execute; this module divides that by measured wall time
and the device peak to publish a live ``paddle_tpu_mfu`` gauge per job
(the training loop, each serving engine), plus a
``paddle_tpu_step_phase_seconds{phase=...}`` histogram family that
partitions every training step's wall time into:

    feed           inline reader + feed assembly (pipeline::host_blocked)
    prefetch_wait  consumer stalls on the FeedPrefetcher
    dispatch       enqueueing the jitted step (includes trace+compile
                   on a cache miss)
    fetch_sync     device->host materialization of fetched values
    device         the residual: wall time not accounted to any host
                   phase — device compute the host successfully hid
                   behind

so one scrape answers "compute-bound or input-bound, and at what MFU":
a large ``feed``/``prefetch_wait`` share is input starvation (ROADMAP
item 4's host_pipeline_vs_compute), a large ``device`` share with low
MFU is the kernel headroom ROADMAP item 2 chases. By construction the
five phases sum to step wall time (host phases are measured, device is
the remainder, clamped at 0 when host work exceeds the wall — e.g. an
overlapped fetch of a previous step).

The phase feed comes from ``profiler.add_event_listener``: CAT_PIPELINE
events accumulate into a process-wide bucket the Trainer drains once
per dispatch. Always-on (no profiler session needed); the whole layer
keys off the same kill switches as the rest of observability —
a disabled default registry, or ``PADDLE_TPU_ATTRIBUTION=0``.

Boundary (KNOWN_GAPS): the accumulator is process-global, so a serving
engine co-resident with a training loop folds its dispatch/fetch events
into the trainer's breakdown. MFU is computed against the peak of the
device JAX reports (``PEAK_FLOPS_BY_DEVICE_KIND``), or
``PADDLE_TPU_PEAK_FLOPS`` when set; a device that is in neither publishes
no ``paddle_tpu_mfu`` at all (a CPU backend has no peak worth dividing
by — tests that read the gauge set the flag).
"""
from __future__ import annotations

import os
import threading
from typing import Dict, Optional

import jax

from .. import profiler

__all__ = ["PHASES", "PHASE_BY_EVENT", "PEAK_FLOPS_BY_DEVICE_KIND",
           "peak_flops", "require_peak_flops",
           "attribution_enabled", "set_attribution_enabled",
           "drain_phases", "mfu_gauge", "model_flops_gauge",
           "phase_histogram"]

#: Peak dense bf16 FLOP/s of ONE chip, keyed by
#: ``jax.devices()[0].device_kind`` exactly as JAX prints it (the first
#: row is the kind chip_smoke.py printed on the attached chip, PR 22).
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16 per
#: chip). The one table every MFU in the tree divides by — a device
#: that is not in it has no default.
PEAK_FLOPS_BY_DEVICE_KIND = {
    "TPU v5 lite": 197e12,
}


def peak_flops() -> Optional[float]:
    """Peak FLOP/s the MFU gauge is normalized against:
    ``PADDLE_TPU_PEAK_FLOPS`` when set (read per call so tests and
    benchmarks can flip it; a malformed value raises ValueError), else
    the table row of the device JAX reports, else None — the caller
    then publishes no MFU."""
    env = os.environ.get("PADDLE_TPU_PEAK_FLOPS")
    if env:
        return float(env)
    return PEAK_FLOPS_BY_DEVICE_KIND.get(jax.devices()[0].device_kind)


def require_peak_flops() -> float:
    """peak_flops() for benchmarks: an unknown device is an error, not
    a default."""
    peak = peak_flops()
    if peak is None:
        raise RuntimeError(
            f"no peak FLOP/s known for device kind "
            f"{jax.devices()[0].device_kind!r}: add it to "
            "observability.attribution.PEAK_FLOPS_BY_DEVICE_KIND with "
            "its source, or set PADDLE_TPU_PEAK_FLOPS")
    return peak


_enabled_override: Optional[bool] = None


def attribution_enabled() -> bool:
    """Kill switch for MFU/phase publication: a programmatic override
    (``set_attribution_enabled``) wins, else ``PADDLE_TPU_ATTRIBUTION``
    (default on). The metrics-registry ``enabled=False`` arm disables
    it too, since every instrument here lives in the registry."""
    if _enabled_override is not None:
        return _enabled_override
    on = os.environ.get("PADDLE_TPU_ATTRIBUTION", "1") != "0"
    if on and not profiler.has_event_listener(_phase_listener):
        # env flipped 0 -> 1 after import: install the listener now, or
        # the phase buckets stay empty and every step reads as 100%
        # device while the MFU gauges publish
        profiler.add_event_listener(_phase_listener)
    return on


def set_attribution_enabled(v: Optional[bool]) -> Optional[bool]:
    """Override the env toggle (None restores env-driven behaviour).
    Also installs/removes the profiler event listener, so that disabling
    restores the listener-free hot path (one list truthiness test per
    event).
    Returns the previous override so callers can restore it."""
    global _enabled_override
    prev = _enabled_override
    _enabled_override = None if v is None else bool(v)
    _sync_listener()
    return prev


#: the published phase set, in scrape-stable order
PHASES = ("feed", "dispatch", "device", "fetch_sync", "prefetch_wait")

#: CAT_PIPELINE event name -> phase. pipeline::prefetch_fill (producer-
#: thread convert+upload) is deliberately absent: that work OVERLAPS
#: device compute, so charging it to the step's serial breakdown would
#: double-count hidden time.
PHASE_BY_EVENT = {
    "pipeline::host_blocked": "feed",
    "pipeline::prefetch_wait": "prefetch_wait",
    "pipeline::dispatch": "dispatch",
    "pipeline::fetch_sync": "fetch_sync",
}


class _PhaseAccumulator:
    """Thread-safe per-phase second totals since the last drain."""

    def __init__(self):
        self._lock = threading.Lock()
        self._seconds: Dict[str, float] = {}

    def add(self, phase: str, seconds: float) -> None:
        with self._lock:
            self._seconds[phase] = self._seconds.get(phase, 0.0) + seconds

    def drain(self) -> Dict[str, float]:
        with self._lock:
            out, self._seconds = self._seconds, {}
        return out


_acc = _PhaseAccumulator()


def _phase_listener(ev: Dict) -> None:
    # attribution_enabled() re-checked per event: belt-and-braces for
    # an env flip after the listener was installed
    if ev.get("cat") != profiler.CAT_PIPELINE or not attribution_enabled():
        return
    phase = PHASE_BY_EVENT.get(ev["name"])
    if phase is not None:
        _acc.add(phase, ev["dur"] / 1e6)


def _sync_listener() -> None:
    """Install the phase listener only while attribution is on, so the
    kill switch restores profiler.py's listener-free disabled path
    (RecordEvent never builds the event dict). Env-var flips AFTER
    import self-heal: 1 -> 0 leaves the listener installed but inert
    (the per-event check above); 0 -> 1 re-installs it at the next
    attribution_enabled() call."""
    if attribution_enabled():
        profiler.add_event_listener(_phase_listener)
    else:
        profiler.remove_event_listener(_phase_listener)


_sync_listener()


def drain_phases() -> Dict[str, float]:
    """Host-phase seconds accumulated since the last drain (the Trainer
    calls this once per dispatch, and once at train() start to reset
    the window)."""
    return _acc.drain()


# ---------------------------------------------------------------------------
# instrument declarations — defined ONCE so the trainer and every
# serving engine agree on name/help/labels (the registry rejects
# conflicting re-registration)
# ---------------------------------------------------------------------------
_MFU_HELP = ("Model FLOPs utilization of the most recent step/batch: "
             "static cost-model FLOPs / wall time / device peak "
             "(by device_kind, or PADDLE_TPU_PEAK_FLOPS); absent for a "
             "device with no known peak.")
_FLOPS_HELP = ("Static cost-model FLOPs per step of the currently "
               "compiled program for this job.")
_PHASE_HELP = ("Per-step wall-time breakdown by phase (feed, dispatch, "
               "device, fetch_sync, prefetch_wait); the phases of one "
               "step sum to its wall time, device is the host-side "
               "residual.")


def mfu_gauge(reg, job: str):
    return reg.gauge("paddle_tpu_mfu", _MFU_HELP, ("job",)) \
        .labels(job=job)


def model_flops_gauge(reg, job: str):
    return reg.gauge("paddle_tpu_model_flops", _FLOPS_HELP, ("job",)) \
        .labels(job=job)


def phase_histogram(reg):
    return reg.histogram("paddle_tpu_step_phase_seconds", _PHASE_HELP,
                         ("phase",))
