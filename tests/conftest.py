"""Test configuration: force the CPU backend with 8 virtual devices so
sharding/mesh tests run without TPU hardware (the driver separately
dry-runs the multi-chip path via __graft_entry__.dryrun_multichip)."""
import os
import sys
import tempfile

os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"

# Point the process-default flight recorder (built ENABLED at
# paddle_tpu.observability import) at a per-run private dir: expected-
# failure tests (golden verifier defects, chaos faults) trigger real
# dumps, and pruning is per-pid so bundles in the host-shared default
# dir would accumulate across runs forever.
if "PADDLE_TPU_FLIGHT_DIR" not in os.environ:
    import atexit
    import shutil
    _flight_dir = tempfile.mkdtemp(prefix="pt_test_flightrec_")
    os.environ["PADDLE_TPU_FLIGHT_DIR"] = _flight_dir
    atexit.register(shutil.rmtree, _flight_dir, ignore_errors=True)

import jax  # noqa: E402

# Tests always run on the host CPU, also when JAX_PLATFORMS is unset on
# a machine with a chip (a test run must never take the chip).
jax.config.update("jax_platforms", "cpu")
# No persistent compile cache under tests: six xdist workers sharing
# <checkout>/.jax_cache would make the run's time depend on what an
# earlier run left there (core/executor.py place_compile_cache still
# sets the directory; nothing is read from or written to it).
jax.config.update("jax_enable_compilation_cache", False)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running (subprocess compile) tests")
    config.addinivalue_line(
        "markers", "chaos: seeded fault-injection tests (deterministic, "
        "fast — they run in tier-1)")


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test builds graphs into fresh default programs and scope,
    under no ambient mesh: ``make_mesh`` sets a process-wide mesh, and
    one left by an earlier test file of the same xdist worker (which
    files share a worker changes from run to run) sharded whatever the
    next test built with ``mesh=None``."""
    import paddle_tpu as pt
    from paddle_tpu.parallel.mesh import set_mesh
    pt.reset_default_programs()
    pt.reset_global_scope()
    set_mesh(None)
    yield


@pytest.fixture(autouse=True)
def no_prefetcher_thread_leak():
    """FeedPrefetcher threads must not outlive their training loop: no
    test may start with one alive, and none may leak one (mirror of the
    fault-injector inertness check below)."""
    import threading
    import time

    def live():
        return [t.name for t in threading.enumerate()
                if t.name.startswith("feed-prefetcher") and t.is_alive()]

    assert not live(), \
        f"prefetcher thread(s) leaked from a previous test: {live()}"
    yield
    # a just-closed prefetcher may need a beat to exit its put poll
    deadline = time.monotonic() + 2.0
    while live() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not live(), f"test leaked prefetcher thread(s): {live()}"


@pytest.fixture(autouse=True)
def no_reader_worker_leak():
    """Reader worker PROCESSES and their shared-memory segments must not
    outlive their test: multiprocess_batch_reader and
    StreamingInputService both spawn multiprocessing children and
    allocate /dev/shm ring slots named ptshm<pid>_* (pid = this
    process); a leak here starves later tests of cores and shm."""
    import glob
    import multiprocessing as _mp
    import time

    def segs():
        return glob.glob(f"/dev/shm/ptshm{os.getpid()}_*")

    assert not segs(), \
        f"shared-memory segment(s) leaked from a previous test: {segs()}"
    yield
    # workers exiting after a service stop may need a beat to be reaped
    deadline = time.monotonic() + 5.0
    while _mp.active_children() and time.monotonic() < deadline:
        time.sleep(0.02)
    leaked = _mp.active_children()
    assert not leaked, f"test leaked reader worker process(es): {leaked}"
    deadline = time.monotonic() + 2.0
    while segs() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert not segs(), \
        f"test leaked shared-memory segment(s): {segs()}"


@pytest.fixture(autouse=True)
def no_fault_injector_leak():
    """The FaultInjector must be inert outside an explicit scope: no test
    may start with one armed, and none may leak one (chaos in one test
    must never bleed into the next)."""
    from paddle_tpu.resilience import faults
    assert faults.active() is None, \
        "a FaultInjector leaked from a previous test"
    yield
    assert faults.active() is None, \
        "test left a FaultInjector installed"
