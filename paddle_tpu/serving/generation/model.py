"""GenerationModel: one decoder-LM's full program set (prefill /
decode-step / re-forward baseline), pinned weights, and per-slot state
in a private scope.

The batch-serving analog is ServableModel (one frozen program); a
generation model is a FAMILY of programs sharing one parameter set by
name, plus persistable per-slot state the decode programs update in
place via donation. A spec names the architecture family that builds
its programs (``FAMILIES``): "transformer" (models/transformer.py
build_decoder_lm: ``kv_cache.*`` state), "hybrid_ssm"
(models/hybrid_ssm.py: KV caches beside convolution windows and
recurrent states, three kinds a slot), "cca_moe" (models/cca_moe.py:
a compressed KV cache beside three convolution windows a layer, and
top-1 experts whose rows each step reports with its tokens) or
"delta_hybrid" (models/delta_hybrid.py: multi-head KV caches beside
three convolution windows and one delta-rule matrix state a linear
layer).
All programs live in one Executor compile cache — hosting N models on
a shared executor (GenerationHost) dedupes nothing but ALSO collides
nothing, because the cache key includes each program's uid/version.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

import numpy as np

from ... import io
from ...core.scope import Scope
from ...executor import Executor, scope_guard
from ...models.transformer import (KV_CACHE_PREFIX, build_decoder_lm,
                                   kv_cache_names)

__all__ = ["GenerationSpec", "GenerationModel", "bucket_for"]


def bucket_for(n: int, buckets) -> Optional[int]:
    """Smallest bucket >= n, or None when n exceeds every bucket."""
    for b in buckets:
        if n <= b:
            return int(b)
    return None


class GenerationSpec:
    """Everything needed to rebuild a generation program set around a
    saved checkpoint — rides ``save_inference_model`` meta (io.py) so
    an artifact is self-describing for token serving."""

    FIELDS = ("vocab_size", "max_seq_len", "slots", "prompt_buckets",
              "cache_buckets", "n_layer", "n_head", "d_model", "d_inner",
              "seed", "eos_id", "kv_cache_layout")
    # what a family other than the default adds: a saved transformer
    # spec holds FIELDS alone, as it always has, and loads as one
    FAMILY_FIELDS = ("family", "arch")

    def __init__(self, vocab_size, max_seq_len, slots=None,
                 prompt_buckets=None, cache_buckets=None,
                 n_layer=2, n_head=4, d_model=64, d_inner=128, seed=0,
                 eos_id=0,
                 kv_cache_layout="[slots, n_head, max_seq_len, d_key]",
                 family="transformer", arch=None):
        """``family`` names the builder (``FAMILIES``); ``arch`` is that
        family's own description of the stack — for "hybrid_ssm" the
        keywords of models/hybrid_ssm.py build_hybrid_lm beyond the
        sizes above: ``arch`` (the published config keys), ``dtypes``,
        ``embedding_std``; the same three for "cca_moe"
        (models/cca_moe.py build_cca_moe_lm) — and None for
        "transformer", which reads
        n_layer, n_head, d_model and d_inner."""
        from ... import flags
        if slots is None:
            slots = int(flags.get("PADDLE_TPU_DECODE_SLOTS"))
        if cache_buckets is None:
            cache_buckets = [
                int(x) for x in
                flags.get("PADDLE_TPU_DECODE_CACHE_BUCKETS").split(",")]
            # the flag default may exceed a small model's max_seq_len
            cache_buckets = [b for b in cache_buckets
                             if b <= int(max_seq_len)] \
                or [int(max_seq_len)]
        if prompt_buckets is None:
            prompt_buckets = list(cache_buckets)
        self.vocab_size = int(vocab_size)
        self.max_seq_len = int(max_seq_len)
        self.slots = int(slots)
        self.prompt_buckets = sorted(set(int(x) for x in prompt_buckets))
        self.cache_buckets = sorted(set(int(x) for x in cache_buckets))
        self.n_layer = int(n_layer)
        self.n_head = int(n_head)
        self.d_model = int(d_model)
        self.d_inner = int(d_inner)
        self.seed = int(seed)
        self.eos_id = int(eos_id)
        self.kv_cache_layout = str(kv_cache_layout)
        if family not in FAMILIES:
            raise ValueError(f"generation family {family!r}: one of "
                             f"{sorted(FAMILIES)}")
        self.family = str(family)
        self.arch = dict(arch) if arch else None

    def to_dict(self) -> Dict:
        fields = self.FIELDS if self.family == "transformer" \
            else self.FIELDS + self.FAMILY_FIELDS
        return {f: getattr(self, f) for f in fields}

    @classmethod
    def from_dict(cls, d: Dict) -> "GenerationSpec":
        return cls(**{f: d[f] for f in cls.FIELDS + cls.FAMILY_FIELDS
                      if f in d})

    def __eq__(self, other):
        return isinstance(other, GenerationSpec) and \
            self.to_dict() == other.to_dict()

    def __repr__(self):
        return f"GenerationSpec({self.to_dict()})"


def _build_transformer(spec: GenerationSpec) -> Dict:
    return build_decoder_lm(
        vocab_size=spec.vocab_size, max_seq_len=spec.max_seq_len,
        slots=spec.slots, prompt_buckets=spec.prompt_buckets,
        cache_buckets=spec.cache_buckets, n_layer=spec.n_layer,
        n_head=spec.n_head, d_model=spec.d_model,
        d_inner=spec.d_inner, seed=spec.seed)


def _build_hybrid_ssm(spec: GenerationSpec) -> Dict:
    from ...models.hybrid_ssm import build_hybrid_lm
    return build_hybrid_lm(
        vocab_size=spec.vocab_size, max_seq_len=spec.max_seq_len,
        slots=spec.slots, prompt_buckets=spec.prompt_buckets,
        cache_buckets=spec.cache_buckets, seed=spec.seed,
        **(spec.arch or {}))


def _build_cca_moe(spec: GenerationSpec) -> Dict:
    from ...models.cca_moe import build_cca_moe_lm
    return build_cca_moe_lm(
        vocab_size=spec.vocab_size, max_seq_len=spec.max_seq_len,
        slots=spec.slots, prompt_buckets=spec.prompt_buckets,
        cache_buckets=spec.cache_buckets, seed=spec.seed,
        **(spec.arch or {}))


def _build_delta_hybrid(spec: GenerationSpec) -> Dict:
    from ...models.delta_hybrid import build_delta_hybrid_lm
    return build_delta_hybrid_lm(
        vocab_size=spec.vocab_size, max_seq_len=spec.max_seq_len,
        slots=spec.slots, prompt_buckets=spec.prompt_buckets,
        cache_buckets=spec.cache_buckets, seed=spec.seed,
        **(spec.arch or {}))


#: family name -> the builder of its program set; each returns what
#: build_decoder_lm returns, and may add "state_kinds" ({kind: [names]})
#: and "state_prefixes" where a slot owns more than KV caches, and
#: "observed" ((mode, bucket) -> {what: (offset, shape)}) where a
#: program's fetch carries more than its tokens
FAMILIES = {"transformer": _build_transformer,
            "hybrid_ssm": _build_hybrid_ssm,
            "cca_moe": _build_cca_moe,
            "delta_hybrid": _build_delta_hybrid}


class GenerationModel:
    """Program set + weights + per-slot state for one decoder LM.

    ``executor``/``run_lock`` follow the ServableModel sharing
    contract: a GenerationHost passes the same pair to every hosted
    model so all their executables live in one compile cache, and runs
    are serialized by one lock (executor internals are not
    thread-safe). The per-model scope keeps weights AND cache state
    private — two hosted models never alias each other's cache."""

    def __init__(self, programs: Dict, spec: GenerationSpec,
                 scope: Optional[Scope] = None,
                 executor: Optional[Executor] = None,
                 run_lock: Optional[threading.Lock] = None,
                 version: Optional[str] = None,
                 init_scope: bool = True):
        if (executor is None) != (run_lock is None):
            raise ValueError("share executor and run_lock together "
                             "(executor internals are serialized by "
                             "the lock)")
        self.programs = programs
        self.spec = spec
        self.scope = scope if scope is not None else Scope()
        self.executor = executor if executor is not None else Executor()
        self._run_lock = run_lock if run_lock is not None \
            else threading.Lock()
        self.version = version
        # every persistable state a slot owns, and its names by kind
        self.state_kinds = programs.get("state_kinds") \
            or {"kv": kv_cache_names(spec.n_layer)}
        self.cache_names = [n for names in self.state_kinds.values()
                            for n in names]
        self._state_prefixes = tuple(programs.get(
            "state_prefixes", (KV_CACHE_PREFIX,)))
        # what the last prefill / decode run reported after its tokens
        # ({what: int array}; {} for a family that reports nothing)
        self._observed_layout = programs.get("observed")
        self.last_observed: Dict[str, np.ndarray] = {}
        self._check_frozen()
        self._verify()
        if init_scope:
            with self._run_lock:
                self.executor.run(programs["startup"], scope=self.scope)

    # ------------------------------------------------------------------
    @classmethod
    def build(cls, spec: GenerationSpec,
              executor: Optional[Executor] = None,
              run_lock: Optional[threading.Lock] = None,
              version: Optional[str] = None) -> "GenerationModel":
        """Fresh model (randomly initialized weights) from a spec."""
        return cls(FAMILIES[spec.family](spec), spec, executor=executor,
                   run_lock=run_lock, version=version)

    @classmethod
    def load(cls, dirname: str, executor: Optional[Executor] = None,
             run_lock: Optional[threading.Lock] = None
             ) -> "GenerationModel":
        """Load a ``save_inference_model`` artifact whose meta carries a
        generation spec: rebuild the program set from the spec (param
        names are deterministic under isolated_name_scope), run startup
        (weights re-randomized, caches zeroed), then overwrite the
        weights from the checkpoint."""
        probe_scope = Scope()
        probe_exe = Executor()
        with scope_guard(probe_scope):
            _prog, _feeds, _fetch, meta = io.load_inference_model(
                dirname, probe_exe, return_meta=True)
        gspec = meta.get("generation_spec")
        if not gspec:
            raise ValueError(
                f"artifact {dirname!r} carries no generation_spec — "
                "save it with io.save_inference_model(..., "
                "generation_spec=model.spec.to_dict()) or "
                "GenerationModel.save()")
        spec = GenerationSpec.from_dict(gspec)
        model = cls.build(spec, executor=executor, run_lock=run_lock,
                          version=meta.get("model_version"))
        # overwrite the fresh random weights with the checkpoint's; the
        # full program's persistable set is exactly the weights (no
        # cache vars), so caches stay zero
        full = model._full(spec.prompt_buckets[-1])
        with scope_guard(model.scope):
            io.load_vars(probe_exe, dirname, full.main,
                         predicate=lambda v: v.persistable)
        return model

    def save(self, dirname: str, model_version: Optional[str] = None
             ) -> str:
        """Freeze the re-forward program + weights + generation spec.
        The full program has no cache ops, so the saved persistable set
        is the weights only — cache state never ships."""
        full = self._full(self.spec.prompt_buckets[-1])
        block = full.main.global_block()
        with scope_guard(self.scope):
            io.save_inference_model(
                dirname, full.feed_names, [block.var(full.fetch_name)],
                self.executor, main_program=full.main,
                model_version=model_version,
                generation_spec=self.spec.to_dict())
        return dirname

    # ------------------------------------------------------------------
    def _built(self):
        return [(mode, bucket, lm) for mode in ("prefill", "decode", "full")
                for bucket, lm in self.programs[mode].items()]

    def _full(self, bucket):
        """The re-forward program of a prompt bucket. A family may
        build these when first asked for (models/served_lm.py OnAsk);
        such a program passes the two gates of the constructor here,
        before its first use."""
        held = self.programs["full"]
        fresh = bucket not in held
        lm = held[bucket]
        if fresh:
            self._check_frozen([("full", bucket, lm)])
            self._verify([("full", bucket, lm)])
        return lm

    def _check_frozen(self, programs=None):
        """Generation programs may write persistable state ONLY under
        the family's state prefixes (kv_cache.*; conv_state.* and
        ssm_state.* too for a hybrid stack) — any other persistable
        write is a training op that would silently mutate pinned
        weights on traffic (the generation analog of
        ServableModel._check_frozen)."""
        offenders = []
        for mode, bucket, lm in programs or self._built():
            for block in lm.main.desc.blocks:
                for op in block.ops:
                    for name in op.output_names():
                        v = block.find_var_recursive(name)
                        if v is not None and v.persistable and \
                                not name.startswith(self._state_prefixes):
                            offenders.append((mode, bucket, op.type, name))
        if offenders:
            raise ValueError(
                "generation program set is not frozen — ops write "
                f"non-cache persistable vars: {offenders}")

    def _verify(self, programs=None):
        """Static verification of every program at load/build time
        (startup included, so the cache vars' zero-fill satisfies the
        uninit-persistable pass). Honors PADDLE_TPU_VERIFY=0."""
        from ...analysis import verify_enabled, verify_program
        if not verify_enabled():
            return
        for mode, bucket, lm in programs or self._built():
            verify_program(
                lm.main, startup=lm.startup,
                feed_names=lm.feed_names,
                fetch_names=[lm.fetch_name],
                program_label=f"generation {mode}[{bucket}]",
            ).raise_if_errors(context="GenerationModel load")

    # ------------------------------------------------------------------
    def _run(self, lm, feed) -> np.ndarray:
        with self._run_lock:
            res = self.executor.run(lm.main, feed=feed,
                                    fetch_list=[lm.fetch_name],
                                    scope=self.scope, sync=True)
        return np.asarray(res[0])

    def _tokens(self, out: np.ndarray, n: int, mode: str, bucket: int
                ) -> np.ndarray:
        """The first ``n`` values a program fetched are its tokens; a
        family may append what the step observed ("observed" of its
        program set: models/cca_moe.py's expert rows and picks), kept
        as ``last_observed`` — host views of the one fetch, no other
        transfer."""
        flat = out.reshape(-1)
        if self._observed_layout is not None:
            tail = flat[n:]
            self.last_observed = {
                what: tail[at:at + int(np.prod(shape))].reshape(shape)
                for what, (at, shape) in
                self._observed_layout(mode, int(bucket)).items()}
        return flat[:n]

    def last_expert_rows(self) -> Optional[np.ndarray]:
        """[experts + 1] of the last decode step: the rows each expert
        was sent, summed over the layers, then the experts any row
        reached, summed over the layers; None for a family without
        experts."""
        return self.last_observed.get("expert_rows")

    def run_prefill(self, prompt: List[int], slot: int) -> int:
        """Full-prompt forward for one request into `slot`'s cache
        rows; returns the first greedy token."""
        s = bucket_for(len(prompt), self.spec.prompt_buckets)
        if s is None:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds the largest "
                f"prompt bucket {self.spec.prompt_buckets[-1]}")
        ids = np.zeros((1, s, 1), np.int64)
        ids[0, :len(prompt), 0] = prompt
        out = self._run(self.programs["prefill"][s], {
            "token_ids": ids,
            "lengths": np.asarray([len(prompt)], np.int64),
            "slot": np.asarray([slot], np.int64)})
        return int(self._tokens(out, 1, "prefill", s)[0])

    def run_decode(self, tokens: np.ndarray, positions: np.ndarray,
                   bucket: int, lengths: Optional[np.ndarray] = None
                   ) -> np.ndarray:
        """One decode step over the whole slot array. tokens:
        [slots] int64 (last emitted token per slot), positions: [slots]
        int64 (cache write position per slot), lengths: [slots] int64
        (live cache rows per slot counting this token: positions + 1,
        the default, or 0 for a slot with no request in it, whose row
        of the result means nothing). Returns [slots] next tokens."""
        lm = self.programs["decode"][int(bucket)]
        positions = positions.astype(np.int64)
        out = self._run(lm, {
            "token_ids": tokens.reshape(self.spec.slots, 1, 1)
            .astype(np.int64),
            "positions": positions,
            "lengths": positions + 1 if lengths is None
            else lengths.astype(np.int64)})
        return self._tokens(out, self.spec.slots, "decode", bucket)

    def run_full(self, token_matrix: np.ndarray, lengths: np.ndarray,
                 bucket: int) -> np.ndarray:
        """Re-forward baseline step: full causal forward over the whole
        (padded) [slots, bucket] token matrix; returns [slots] next
        tokens at each row's last real position."""
        lm = self._full(int(bucket))
        out = self._run(lm, {
            "token_ids": token_matrix.reshape(
                self.spec.slots, int(bucket), 1).astype(np.int64),
            "lengths": lengths.astype(np.int64)})
        return out.reshape(-1)

    def state_bytes(self) -> Dict[str, int]:
        """Bytes reserved for per-slot state, by kind (kv / conv /
        ssm), whatever the slots hold."""
        return {kind: int(sum(self.scope.get(n).nbytes for n in names))
                for kind, names in self.state_kinds.items()}

    def last_cost(self):
        """Static cost of the most recent dispatch's executable."""
        return self.executor.last_cost

    def last_memory(self):
        """Static memory plan (analysis/memory.py MemoryReport) of the
        most recent dispatch's executable."""
        return getattr(self.executor, "last_memory", None)

    # ------------------------------------------------------------------
    def serve(self, config=None, metrics=None, health=None,
              mode: str = "cached"):
        """Create (but do not start) a GenerationEngine bound to this
        model."""
        from .engine import GenerationEngine
        engine = GenerationEngine(self, config=config, metrics=metrics,
                                  health=health, mode=mode)
        engine.metrics.state_bytes(self.state_bytes())
        return engine
