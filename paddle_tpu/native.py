"""ctypes binding to the native runtime library (native/*.cc).

The reference binds its C++ runtime to Python with pybind11
(reference: paddle/fluid/pybind/pybind.cc:74-185); pybind11 is not in this
image, so the native layer exposes a C ABI and this module wraps it with
ctypes. The library is built lazily via `make` on first use when it is missing
or older than any native/*.cc.
"""
from __future__ import annotations

import ctypes
import glob
import os
import subprocess
import threading

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "build", "libpaddle_tpu_native.so")

_lib = None
_lock = threading.Lock()


def _build():
    subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                   capture_output=True)


def _stale() -> bool:
    """True when the library is missing or any native/*.cc is newer
    than it, so a prebuilt .so can never shadow changed sources."""
    if not os.path.exists(_LIB_PATH):
        return True
    built = os.path.getmtime(_LIB_PATH)
    return any(os.path.getmtime(src) > built
               for src in glob.glob(os.path.join(_NATIVE_DIR, "*.cc")))


def lib() -> ctypes.CDLL:
    """Load (building if needed) the native library; idempotent."""
    global _lib
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None:
            return _lib
        if _stale():
            _build()
        l = ctypes.CDLL(_LIB_PATH)

        l.rio_last_error.restype = ctypes.c_char_p
        l.rio_writer_open.restype = ctypes.c_void_p
        l.rio_writer_open.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                      ctypes.c_int]
        l.rio_writer_write.restype = ctypes.c_int
        l.rio_writer_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       ctypes.c_uint64]
        l.rio_writer_close.restype = ctypes.c_int64
        l.rio_writer_close.argtypes = [ctypes.c_void_p]
        l.rio_scanner_open.restype = ctypes.c_void_p
        l.rio_scanner_open.argtypes = [ctypes.c_char_p]
        l.rio_scanner_next.restype = ctypes.POINTER(ctypes.c_char)
        l.rio_scanner_next.argtypes = [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_uint64)]
        l.rio_scanner_close.argtypes = [ctypes.c_void_p]

        l.dl_open.restype = ctypes.c_void_p
        l.dl_open.argtypes = [ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
                              ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
                              ctypes.c_int, ctypes.c_int]
        l.dl_next.restype = ctypes.POINTER(ctypes.c_char)
        l.dl_next.argtypes = [ctypes.c_void_p,
                              ctypes.POINTER(ctypes.c_uint64)]
        l.dl_error.restype = ctypes.c_char_p
        l.dl_error.argtypes = [ctypes.c_void_p]
        l.dl_close.argtypes = [ctypes.c_void_p]

        # master task dispatcher (native/master.cc)
        l.ms_create.restype = ctypes.c_void_p
        l.ms_create.argtypes = [ctypes.c_double, ctypes.c_int]
        l.ms_destroy.argtypes = [ctypes.c_void_p]
        l.ms_set_dataset.restype = ctypes.c_int
        l.ms_set_dataset.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_int]
        l.ms_get_task.restype = ctypes.POINTER(ctypes.c_char)  # malloc-copy; free via ms_free
        l.ms_get_task.argtypes = [
            ctypes.c_void_p, ctypes.c_double,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_int32)]
        l.ms_task_finished.restype = ctypes.c_int
        l.ms_task_finished.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                       ctypes.c_int32]
        l.ms_task_failed.restype = ctypes.c_int
        l.ms_task_failed.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                     ctypes.c_int32]
        l.ms_tick.restype = ctypes.c_int
        l.ms_tick.argtypes = [ctypes.c_void_p, ctypes.c_double]
        l.ms_new_pass.restype = ctypes.c_int
        l.ms_new_pass.argtypes = [ctypes.c_void_p, ctypes.c_int]
        l.ms_count.restype = ctypes.c_int64
        l.ms_count.argtypes = [ctypes.c_void_p, ctypes.c_int]
        l.ms_request_save.restype = ctypes.c_int
        l.ms_request_save.argtypes = [ctypes.c_void_p, ctypes.c_double,
                                      ctypes.c_double]
        l.ms_snapshot.restype = ctypes.POINTER(ctypes.c_char)
        l.ms_snapshot.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_uint64)]
        l.ms_free.argtypes = [ctypes.c_void_p]
        l.ms_recover.restype = ctypes.c_int
        l.ms_recover.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                 ctypes.c_uint64]

        # program IR (native/ir.cc)
        l.ir_last_error.restype = ctypes.c_char_p
        l.ir_from_json.restype = ctypes.c_void_p
        l.ir_from_json.argtypes = [ctypes.c_char_p]
        l.ir_to_json.restype = ctypes.POINTER(ctypes.c_char)
        l.ir_to_json.argtypes = [ctypes.c_void_p]
        l.ir_free.argtypes = [ctypes.c_void_p]
        l.ir_free_str.argtypes = [ctypes.POINTER(ctypes.c_char)]
        l.ir_save.restype = ctypes.c_int
        l.ir_save.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        l.ir_load.restype = ctypes.c_void_p
        l.ir_load.argtypes = [ctypes.c_char_p]
        l.ir_prune.restype = ctypes.c_void_p
        l.ir_prune.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                               ctypes.c_char_p]
        l.ir_liveness.restype = ctypes.POINTER(ctypes.c_char)
        l.ir_liveness.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        l.ir_validate.restype = ctypes.POINTER(ctypes.c_char)
        l.ir_validate.argtypes = [ctypes.c_void_p]
        _lib = l
    return _lib


def _ir_take_str(ptr) -> str:
    """Copy a malloc'd char* result and free it via ir_free_str."""
    s = ctypes.cast(ptr, ctypes.c_char_p).value.decode()
    lib().ir_free_str(ptr)
    return s


class ProgramIR:
    """Native program handle (native/ir.cc). Methods mirror the C ABI:
    JSON <-> native graph, PTIR binary save/load, prune, liveness,
    validate. Raises RuntimeError with ir_last_error on failure."""

    def __init__(self, handle):
        if not handle:
            raise RuntimeError("native ir: "
                               + lib().ir_last_error().decode())
        self._h = handle

    @classmethod
    def from_json(cls, text: str) -> "ProgramIR":
        return cls(lib().ir_from_json(text.encode()))

    @classmethod
    def load(cls, path: str) -> "ProgramIR":
        return cls(lib().ir_load(str(path).encode()))

    def to_json(self) -> str:
        return _ir_take_str(lib().ir_to_json(self._h))

    def save(self, path: str) -> None:
        if lib().ir_save(self._h, str(path).encode()) != 0:
            raise RuntimeError("native ir save: "
                               + lib().ir_last_error().decode())

    def prune(self, feed_names, fetch_names) -> "ProgramIR":
        return ProgramIR(lib().ir_prune(
            self._h, "\n".join(feed_names).encode(),
            "\n".join(fetch_names).encode()))

    def liveness(self, skip_names=()) -> list:
        import json as _json
        return _json.loads(_ir_take_str(lib().ir_liveness(
            self._h, "\n".join(skip_names).encode())))

    def validate(self) -> str:
        """Empty string when the program is well-formed."""
        return _ir_take_str(lib().ir_validate(self._h))

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        if h and _lib is not None:
            _lib.ir_free(h)


def last_error() -> str:
    return lib().rio_last_error().decode()
