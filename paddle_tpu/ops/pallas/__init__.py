"""Pallas TPU kernels for hot ops.

The reference hand-writes CUDA for its fused hot ops (fused LSTM
paddle/cuda/src/hl_cuda_lstm.cu, top-k cuda/src/hl_top_k.cu, attention-era
compositions in nets.py). The TPU-native analogue is a small library of
Pallas kernels; everything else rides XLA fusion:

  flash_attention    tiled attention, forward and backward
  fused_lstm / gru   the recurrent time loop, state kept in VMEM
  kv_cache_append    the decode step's cache append: every slot's new
                     K or V row in one call, the cache updated in place
  decode_attention   the decode step's attention over the KV cache: one
                     query row a slot against the blocks of that slot's
                     cache that hold live keys, and no others

All kernels run in interpret mode on CPU (tests) and compiled on TPU.
"""
import os

import jax


def interpret_default() -> bool:
    """Interpret kernels off-TPU (tests); compile on real hardware. On
    a TPU backend this is False, always: there interpret mode is
    reachable only through an explicit ``interpret=True`` argument
    (tests). chip_smoke.py asserts it on the chip and reads the kernels
    out of the compiled HLO — the proof of which path ran is the
    ``tpu_custom_call``, not this branch."""
    return jax.default_backend() != "tpu"


def pallas_dispatch(knob_env: str, default: str):
    """The one policy for op-level kernel dispatch, read when the op's
    rule is traced (and again by its grad op, which differentiates the
    same rule): returns (enabled, interpret). "1" enables on TPU only,
    "force" enables anywhere via interpret mode (test coverage), "0"
    disables.
    """
    knob = os.environ.get(knob_env, default)
    if knob == "force":
        return True, None          # None -> interpret_default() inside
    return (knob == "1" and jax.default_backend() == "tpu"), False


from .flash_attention import flash_attention  # noqa: E402

__all__ = ["flash_attention", "interpret_default", "pallas_dispatch"]
