"""C-host inference execution (reference:
paddle/capi/main.h:27 + capi/examples/model_inference): a C program
loads the exported PTIR through the native C ABI, validates it, and
executes a forward pass through the embedded runtime, returning the
output into C memory. The test builds/saves a model, compiles the demo,
runs it, and checks the C-side output against the Python-side forward
to float32 precision."""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")

IN_DIM, OUT_DIM = 16, 4


def _demo_input():
    # the exact pattern native/capi_demo.c fills its C buffer with
    return (np.arange(IN_DIM) % 7).astype(np.float32) * 0.25 - 0.5


@pytest.fixture(scope="module")
def demo_binary():
    r = subprocess.run(["make", "capi_demo"], cwd=NATIVE,
                       capture_output=True, text=True)
    if r.returncode != 0:
        pytest.fail(f"capi_demo build failed:\n{r.stdout}\n{r.stderr}")
    return os.path.join(NATIVE, "build", "capi_demo")


def test_c_host_loads_ptir_and_runs_forward(tmp_path, demo_binary):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [IN_DIM], dtype="float32")
        h = layers.fc(x, size=8, act="tanh")
        out = layers.softmax(layers.fc(h, size=OUT_DIM))
    exe = pt.Executor()
    exe.run(startup)
    model_dir = str(tmp_path / "model")
    pt.io.save_inference_model(model_dir, ["x"], [out], exe, main)
    assert os.path.exists(os.path.join(model_dir, "__model__")), \
        "PTIR artifact missing (native lib not built?)"

    # Python-side expectation on the same input
    (expected,) = exe.run(main, feed={"x": _demo_input()[None, :]},
                          fetch_list=[out])
    expected = np.asarray(expected).reshape(-1)

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in sys.path if "site-packages" in p])
    r = subprocess.run(
        [demo_binary, REPO, model_dir, str(IN_DIM), str(OUT_DIM)],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "PTIR ok" in r.stdout, r.stdout

    m = re.search(r"forward ok:((?: -?\d+\.\d+)+)", r.stdout)
    assert m, r.stdout
    got = np.array([float(v) for v in m.group(1).split()], np.float32)
    assert got.shape == (OUT_DIM,)
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-6)
    # softmax output: a real forward pass, not garbage memory
    assert abs(got.sum() - 1.0) < 1e-4
