"""Generation metrics: the token-serving analog of ServingMetrics.

Each GenerationMetrics instance claims one ``engine="<label>"`` series
in the shared ``paddle_tpu_decode_*`` families; a GenerationHost
additionally publishes per-model routing families under its own
``host``/``model`` labels (host.py). MFU rides the SAME attribution
families the trainer and batch-serving engines use, under a
``job="engine_gen_<label>"`` series — decode executables get the
cached-attention cost rules (analysis/cost_model.py), so the gauge
stays honest for single-token steps.
"""
from __future__ import annotations

import itertools
from typing import Dict, Optional

from ...observability.registry import MetricsRegistry, default_registry

__all__ = ["GenerationMetrics"]

#: monotonically assigned `engine` label values, process-wide (its own
#: pool — batch-serving engines number theirs independently)
_engine_ids = itertools.count()


class GenerationMetrics:
    """All generation-side observability in one place, published under
    ``paddle_tpu_decode_*{engine="gen_<n>"}``:

    - requests/tokens/steps/prefills: volume counters (tokens counts
      GENERATED tokens only, not prompt tokens)
    - retired_total{reason}: every request leaves the slot array
      exactly once — eos, max_tokens, length (hit max_seq_len),
      aborted (breaker trip / non-drain stop), error
    - shed_total{reason}: every request turned away BEFORE taking a
      slot — circuit_open, queue_full, model_budget (host routing)
    - kv_blocks_total{state}: cache blocks a cached step's attention
      reads (a live row in them) and skips (the rest under the bucket)
    - expert_rows_total{expert} / experts_read_total: the rows a
      decode step's routing sent each expert and the experts a step
      read, both summed over the layers (a model with expert layers
      alone: the series appear with its first step)
    - step_seconds / prefill_seconds: device step wall time
    - queue_wait_seconds / ttft_seconds: each retired request's wait
      for a slot and time to first token, from the timestamps on its
      GenerationFuture
    - slots_active / slots_total: continuous-batching occupancy
    - loop_*: the driver thread's own account of its passes, from its
      thread's clocks (``loop_passes``)
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 label: Optional[str] = None):
        reg = registry if registry is not None else default_registry()
        self.registry = reg
        self.engine_label = label or f"gen_{next(_engine_ids)}"
        lab = {"engine": self.engine_label}
        self._owned_families = []

        def counter(name, help):
            fam = reg.counter(name, help, ("engine",))
            self._owned_families.append(fam)
            return fam.labels(**lab)

        def gauge(name, help):
            fam = reg.gauge(name, help, ("engine",))
            self._owned_families.append(fam)
            return fam.labels(**lab)

        def histogram(name, help):
            fam = reg.histogram(name, help, ("engine",))
            self._owned_families.append(fam)
            return fam.labels(**lab)

        self.requests = counter(
            "paddle_tpu_decode_requests_total",
            "Generation requests admitted into the continuous-batching "
            "queue.")
        self.tokens = counter(
            "paddle_tpu_decode_tokens_total",
            "Tokens generated (decode-step outputs delivered to live "
            "slots; prompt tokens are not counted).")
        self.steps = counter(
            "paddle_tpu_decode_steps_total",
            "Decode steps dispatched (one bucketed single-token "
            "executable run over the whole slot array).")
        self.prefills = counter(
            "paddle_tpu_decode_prefills_total",
            "Prefill executions (full-prompt forward writing one "
            "request's KV-cache slot).")
        self._retired_family = reg.counter(
            "paddle_tpu_decode_retired_total",
            "Requests retired from the in-flight slot array, by "
            "reason: eos, max_tokens, length (max_seq_len reached), "
            "aborted (breaker trip or non-drain stop delivered partial "
            "tokens), error.", ("engine", "reason"))
        self._shed_family = reg.counter(
            "paddle_tpu_decode_shed_total",
            "Generation requests shed before taking a slot, by reason: "
            "circuit_open (breaker), queue_full (engine queue "
            "capacity), model_budget (per-model host admission).",
            ("engine", "reason"))
        self._kv_blocks_family = reg.counter(
            "paddle_tpu_decode_kv_blocks_total",
            "KV-cache blocks of the cached decode steps' attention, a "
            "block count a step (one count serves K, V and every "
            "layer), from the slots' live lengths alone: read (blocks "
            "that hold a live row, which the length-bounded kernel "
            "reads) and skipped (the rest of the blocks under the "
            "step's bucket, which it leaves; attention composed over a "
            "slice reads those too).", ("engine", "state"))
        self._state_bytes_family = reg.gauge(
            "paddle_tpu_decode_state_bytes",
            "Bytes reserved on the device for per-slot state, by kind: "
            "kv (the attention layers' KV caches), conv (the "
            "state-space or linear-attention layers' convolution "
            "windows), ssm (a state-space layer's recurrent state) and "
            "delta (a delta-rule layer's matrix state); reserved for "
            "every slot to its limit, whatever the slots hold.",
            ("engine", "kind"))
        self.step_seconds = histogram(
            "paddle_tpu_decode_step_seconds",
            "Wall time of one decode step (dispatch to materialized "
            "next tokens).")
        self.prefill_seconds = histogram(
            "paddle_tpu_decode_prefill_seconds",
            "Wall time of one prefill (full-prompt forward + KV-cache "
            "slot write).")
        self.queue_wait_seconds = histogram(
            "paddle_tpu_decode_queue_wait_seconds",
            "Time a retired request waited for a slot: submit accepted "
            "it (enqueued_at) until a slot was taken for its prefill "
            "(admitted_at).")
        self.ttft_seconds = histogram(
            "paddle_tpu_decode_ttft_seconds",
            "Time to first token of a retired request, as the engine "
            "saw it: submit accepted it (enqueued_at) until its first "
            "generated token was delivered (first_token_at).")
        self.slots_active = gauge(
            "paddle_tpu_decode_slots_active",
            "In-flight batch slots occupied at the last decode-step "
            "boundary.")
        self.slots_total = gauge(
            "paddle_tpu_decode_slots_total",
            "Slot capacity of the continuous-batching engine.")
        # the driver loop's passes that admitted or stepped, device
        # wait taken out of wall (loop_passes)
        self._loop = {
            "passes": counter(
                "paddle_tpu_decode_loop_passes_total",
                "Passes of the engine's driver loop that admitted or "
                "stepped (a pass that only waited for work counts "
                "nowhere in the decode_loop families)."),
            "wall_seconds": counter(
                "paddle_tpu_decode_loop_wall_seconds_total",
                "Wall seconds of the driver loop's passes, from the "
                "head of a pass (taking the queue's lock) to the end "
                "of its iteration, less the loop thread's waits for "
                "the device."),
            "cpu_seconds": counter(
                "paddle_tpu_decode_loop_cpu_seconds_total",
                "CPU seconds of the driver loop's thread over the same "
                "passes, what it burns inside a wait for the device "
                "included (the fetched array's conversion): wall less "
                "cpu is the time the thread was neither waiting for "
                "the device nor running (the interpreter's lock, "
                "another lock, the scheduler), read low by that."),
            "device_wait_seconds": counter(
                "paddle_tpu_decode_loop_device_wait_seconds_total",
                "Wall seconds the driver loop's thread waited for the "
                "device: its pipeline::fetch_sync spans, a prefill's "
                "and a step's alike."),
            "voluntary_switches": counter(
                "paddle_tpu_decode_loop_voluntary_switches_total",
                "Context switches the driver loop's thread asked for "
                "over its passes (it blocked: the device wait, a lock, "
                "the interpreter's lock)."),
            "involuntary_switches": counter(
                "paddle_tpu_decode_loop_involuntary_switches_total",
                "Context switches the driver loop's thread did not ask "
                "for over its passes (pre-empted on its core)."),
        }
        # lazy attribution registration, same contract as ServingMetrics
        self._attr_job = f"engine_gen_{self.engine_label}"
        self.mfu = None
        self.model_flops = None
        # as lazy: a model without expert layers leaves no series
        self._expert_rows_family = None
        self.experts_read = None

    def retired(self, reason: str, future=None) -> None:
        """One request left the engine. ``future`` (its
        GenerationFuture, for a request that held a slot) feeds the two
        latency histograms from the timestamps it carries."""
        self._retired_family.labels(engine=self.engine_label,
                                    reason=reason).inc()
        if future is not None and future.admitted_at is not None:
            self.queue_wait_seconds.record(
                future.admitted_at - future.enqueued_at)
            if future.first_token_at is not None:
                self.ttft_seconds.record(
                    future.first_token_at - future.enqueued_at)

    def loop_passes(self, passes: int, wall: float, cpu: float,
                    device_wait: float, voluntary: int,
                    involuntary: int) -> None:
        """A stretch of passes of the driver loop that admitted or
        stepped, as its own thread's clocks read it (engine.py
        _account): ``wall`` with the waits for the device taken out."""
        loop = self._loop
        loop["passes"].inc(passes)
        loop["wall_seconds"].inc(wall)
        loop["cpu_seconds"].inc(cpu)
        loop["device_wait_seconds"].inc(device_wait)
        loop["voluntary_switches"].inc(voluntary)
        loop["involuntary_switches"].inc(involuntary)

    def shed(self, reason: str) -> None:
        self._shed_family.labels(engine=self.engine_label,
                                 reason=reason).inc()

    def kv_blocks(self, read: int, under_bound: int) -> None:
        """One cached decode step's attention: blocks with a live row
        in them, of the blocks under the step's bucket."""
        for state, n in (("read", read), ("skipped", under_bound - read)):
            self._kv_blocks_family.labels(engine=self.engine_label,
                                          state=state).inc(n)

    def expert_rows(self, rows) -> None:
        """One decode step's routing (GenerationModel.last_expert_rows):
        ``rows`` [experts + 1], the rows each expert was sent summed
        over the layers, then the experts any row reached — whose
        weights the step read — summed over the layers."""
        if self._expert_rows_family is None:
            self._expert_rows_family = self.registry.counter(
                "paddle_tpu_decode_expert_rows_total",
                "Rows the decode steps' routing sent each expert, "
                "summed over the expert layers: a live slot's token is "
                "one row a layer; an empty slot's is none.",
                ("engine", "expert"))
            fam = self.registry.counter(
                "paddle_tpu_decode_experts_read_total",
                "Experts the decode steps read the weights of, summed "
                "over the expert layers and the steps: an expert a "
                "step's routing sent no row is not read.", ("engine",))
            self._owned_families.append(fam)
            self.experts_read = fam.labels(engine=self.engine_label)
        for expert, n in enumerate(rows[:-1]):
            if n:
                self._expert_rows_family.labels(
                    engine=self.engine_label, expert=str(expert)).inc(int(n))
        self.experts_read.inc(int(rows[-1]))

    def state_bytes(self, by_kind: Dict[str, int]) -> None:
        """What the model this engine serves reserves a kind of
        per-slot state (GenerationModel.state_bytes)."""
        for kind, n in by_kind.items():
            self._state_bytes_family.labels(engine=self.engine_label,
                                            kind=kind).set(n)

    def _by_reason(self, family) -> Dict[str, float]:
        out = {}
        for key, child in family.samples():
            if key[0] == self.engine_label:
                out[key[1]] = child.value
        return out

    def set_mfu(self, mfu: float, flops: float) -> None:
        """Publish live decode-step MFU + static per-step FLOPs (lazy
        registration so the attribution kill switch leaves no
        zero-valued series — see ServingMetrics.set_mfu)."""
        if self.mfu is None:
            from ...observability import attribution as _attr
            self.model_flops = _attr.model_flops_gauge(
                self.registry, self._attr_job)
            self.mfu = _attr.mfu_gauge(self.registry, self._attr_job)
        self.mfu.set(mfu)
        self.model_flops.set(flops)

    def retire(self) -> None:
        """Drop every series this engine claimed (host version
        retirement — same cardinality contract as
        ServingMetrics.retire)."""
        key = (self.engine_label,)
        for fam in self._owned_families:
            fam.discard(key)
        for family in (self._retired_family, self._shed_family,
                       self._kv_blocks_family, self._state_bytes_family,
                       self._expert_rows_family):
            for k, _ in family.samples() if family is not None else ():
                if k[0] == self.engine_label:
                    family.discard(k)
        if self.mfu is not None:
            for name in ("paddle_tpu_mfu", "paddle_tpu_model_flops"):
                fam = self.registry.get(name)
                if fam is not None:
                    fam.discard((self._attr_job,))

    def stats(self, executor=None) -> Dict:
        out = {
            "requests": self.requests.value,
            "tokens": self.tokens.value,
            "steps": self.steps.value,
            "prefills": self.prefills.value,
            "slots_active": self.slots_active.value,
            "slots_total": self.slots_total.value,
            "step_seconds": self.step_seconds.snapshot(),
            "prefill_seconds": self.prefill_seconds.snapshot(),
            "queue_wait_seconds": self.queue_wait_seconds.snapshot(),
            "ttft_seconds": self.ttft_seconds.snapshot(),
            "retired_by_reason": self._by_reason(self._retired_family),
            "shed_by_reason": self._by_reason(self._shed_family),
            "kv_blocks_by_state": self._by_reason(self._kv_blocks_family),
            "state_bytes_by_kind": self._by_reason(self._state_bytes_family),
            "mfu": self.mfu.value if self.mfu is not None else 0.0,
            "loop": {k: c.value for k, c in self._loop.items()},
        }
        if self._expert_rows_family is not None:
            out["expert_rows_by_expert"] = self._by_reason(
                self._expert_rows_family)
            out["experts_read"] = self.experts_read.value
        if executor is not None:
            cs = dict(executor.cache_stats)
            total = cs["hits"] + cs["misses"]
            cs["hit_rate"] = round(cs["hits"] / total, 6) if total else 0.0
            out["compile_cache"] = cs
        return out
