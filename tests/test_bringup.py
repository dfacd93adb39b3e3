"""Nothing hides the device (PR 22): a TPUPlace means a TPU, the chip
entry points refuse a machine without one, the compile cache is placed
from outside or at one fixed path, and a stale native library cannot
shadow its sources. What only a chip can show is chip_smoke.py's job."""
import os
import subprocess
import sys

import jax
import pytest

import paddle_tpu as pt
from paddle_tpu import native
from paddle_tpu.core import executor as core_executor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_tpu_place_raises_without_a_tpu():
    with pytest.raises(RuntimeError, match="needs TPU device 0"):
        pt.Executor(pt.TPUPlace())
    with pytest.raises(RuntimeError, match="needs TPU device 3"):
        # the base every executor (ParallelExecutor too) constructs through
        core_executor.Executor(pt.CUDAPlace(3))
    # CPUPlace (and no place) check nothing: JAX picks the backend
    assert pt.Executor(pt.CPUPlace()).place is not None
    assert pt.Executor().place is None


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
        monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code (JAX reads
    the variable itself). Unset: <checkout>/.jax_cache, a fixed path —
    the directory is part of the cache key."""
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            pt.Executor()
            assert jax.config.jax_compilation_cache_dir is None
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            pt.Executor()
            assert jax.config.jax_compilation_cache_dir == \
                os.path.join(REPO, ".jax_cache")
            assert core_executor.place_compile_cache() == \
                os.path.join(REPO, ".jax_cache")      # idempotent
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_no_other_code_sets_a_cache_directory():
    hits = []
    for root, _dirs, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as f:
                    if "compilation_cache_dir" in f.read():
                        hits.append(os.path.relpath(path, REPO))
    assert hits == ["paddle_tpu/core/executor.py"]


@pytest.mark.parametrize("state, stale", [
    ("missing", True), ("older_than_a_source", True), ("fresh", False)])
def test_native_library_rebuilds_when_a_source_is_newer(
        monkeypatch, tmp_path, state, stale):
    src = tmp_path / "recordio.cc"
    src.write_text("// source")
    lib = tmp_path / "build" / "libpaddle_tpu_native.so"
    if state != "missing":
        lib.parent.mkdir()
        lib.write_text("")
        then = os.path.getmtime(src) + (-10 if stale else 10)
        os.utime(lib, (then, then))
    monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_LIB_PATH", str(lib))
    assert native._stale() is stale


@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_chip_entry_points_refuse_a_machine_without_a_tpu(script):
    """No fallback to the CPU under a device metric's name: non-zero
    exit and nothing on stdout."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, script)], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


@pytest.mark.slow
def test_chip_smoke_rehearsal_passes_and_never_prints_the_success_line():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--rehearse"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert '"rehearsal": "passed"' in last and '"ok": false' in last
    assert not any(ln.startswith('{"ok": true')
                   for ln in proc.stdout.splitlines())
