"""Top-level Executor + Places (reference: python/paddle/fluid/executor.py
and platform/place.h). JAX picks the backend for the whole process
(``JAX_PLATFORMS``; a TPU when one is attached), so a place never moves
the computation. ``TPUPlace`` is an assertion: an Executor built with
one raises unless the default backend is a TPU holding that device.
``CPUPlace`` is accepted for scripts written against the reference and
checks nothing."""
from __future__ import annotations

from .core.executor import Executor as _CoreExecutor
from .core.executor import StepResult  # noqa: F401 — public re-export


class CPUPlace:
    def __repr__(self):
        return "CPUPlace"


class TPUPlace:
    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def __repr__(self):
        return f"TPUPlace({self.device_id})"

    def require(self):
        """Raise unless JAX's default backend is a TPU with this
        device — called by Executor.__init__."""
        import jax
        devices = jax.devices()
        if devices[0].platform != "tpu" or \
                not 0 <= self.device_id < len(devices):
            raise RuntimeError(
                f"{self!r} needs TPU device {self.device_id}, but JAX's "
                f"default backend is {devices[0].platform!r} with "
                f"{len(devices)} device(s) ({devices[0].device_kind}); "
                "programs would run there instead. Run on a machine "
                "with the chip, or build the Executor without a place.")


# Alias kept for scripts written against the reference's CUDAPlace.
CUDAPlace = TPUPlace


class Executor(_CoreExecutor):
    pass


def scope_guard(scope):
    import contextlib
    from .core import scope as scope_mod

    @contextlib.contextmanager
    def guard():
        old = scope_mod._global_scope
        scope_mod._global_scope = scope
        try:
            yield
        finally:
            scope_mod._global_scope = old
    return guard()
