"""Driver-contract tests for __graft_entry__.dryrun_multichip.

Round-1 regression: the driver imports and calls dryrun_multichip(n)
under whatever JAX platform the environment initialized (possibly one
chip); the function must self-bootstrap an n-device virtual CPU
platform — in-process when the backend is still configurable, via a
fresh subprocess when it is not.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The dryrun's scaling-model section (bench-shape 64-device compiles,
# ~4 min) has its own dedicated test (tests/test_scaling_model.py);
# these driver-contract tests turn it off to keep the suite's wall
# clock sane. Subprocess fallbacks inherit the env var.
os.environ["PADDLE_TPU_DRYRUN_SCALING"] = "0"


def test_dryrun_8_inprocess_matches_conftest_devices():
    # conftest pins 8 virtual CPU devices, so n=8 runs fully in-process.
    sys.path.insert(0, REPO)
    import __graft_entry__ as g
    g.dryrun_multichip(8)


@pytest.mark.slow
def test_dryrun_16_subprocess_fallback():
    # conftest initialized the backend with 8 devices; n=16 cannot be
    # satisfied in-process, so dryrun must re-exec and still succeed.
    sys.path.insert(0, REPO)
    import __graft_entry__ as g
    g.dryrun_multichip(16)


def test_dryrun_under_preinitialized_small_platform():
    # Exact round-1 failure mode, reproduced end-to-end: a fresh
    # interpreter initializes a 1-device backend BEFORE calling
    # dryrun_multichip(8). Must fall back to a subprocess and pass.
    code = (
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "assert len(jax.devices()) == 1\n"
        f"import sys; sys.path.insert(0, {REPO!r})\n"
        "import __graft_entry__ as g\n"
        "g.dryrun_multichip(8)\n"
    )
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # no virtual devices in the child
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                          text=True, timeout=1500)
    assert proc.returncode == 0, proc.stdout[-2000:]
    assert "mesh=(2, 2, 2)" in proc.stdout
    assert "pipeline" in proc.stdout
