"""The command end to end at toy sizes, as the driver calls it: one
subprocess a run, the result on the last line of stdout. A rehearsal
can never name a TPU, and the real command refuses to run without one
or without the program."""
import json
import os
import subprocess
import sys

import pytest

import chipbench

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    chipbench.__file__)))

ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           XLA_FLAGS="--xla_force_host_platform_device_count=4")
ENV.pop("JAX_COMPILATION_CACHE_DIR", None)


def _run(root, *args, timeout=240):
    return subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"), *args],
        cwd=root, env=ENV, capture_output=True, text=True,
        timeout=timeout)


def _result(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    return line


@pytest.mark.parametrize("cell, trace, expect", [
    ("transformer-base.train-s2048", 0, {"train_tokens_per_s", "setup_s"}),
    ("transformer-base.train-mesh-dp2tp2", 1,
     {"input_wait_ms.train", "host_step_ms.train", "first_step_other_s",
      "compile_backend_s"}),
    ("decoder-lm-base.serve-chat", 0,
     {"serve_tokens_per_s", "ttft_ms_p95", "tpot_ms_p95", "setup_s"}),
    ("decoder-lm-base.serve-chat", 1,
     {"decode_step_ms.serve", "queue_wait_ms_p95.serve",
      "kv_live_share_pct.serve", "first_step_other_s",
      "compile_backend_s"}),
])
def test_rehearsal_of_each_driver_kind(cell, trace, expect):
    line = _result(_run(REPO, "--workload", cell, "--seed", "3000000001",
                        "--seconds", "1.5", "--trace", str(trace),
                        "--rehearse"))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # off the chip there is no device plane: device-trace metrics are
    # left out of the line, never reported from host numbers
    assert set(line["metrics"]) == expect
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert line["rehearsal"] is True
    assert line["device"]["platform"] != "tpu"
    assert "busy_s" not in line["device"]
    assert line["device"]["count"] == (4 if "mesh" in cell else 1)


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    proc = _run(REPO, "--workload", "transformer-base.train-s256",
                "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_without_the_program_the_command_fails(bench_copy):
    proc = _run(str(bench_copy), "--workload",
                "transformer-base.train-s256", "--seed", "1",
                "--seconds", "1", "--trace", "0", "--rehearse")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_a_rehearsal_refuses_a_tpu():
    from chipbench import device

    class Fake:
        platform, device_kind = "tpu", "TPU v5 lite"

    with pytest.raises(device.DeviceError):
        device.describe([Fake()], 1, rehearse=True)
    with pytest.raises(device.DeviceError):
        device.describe([Fake()], 4, rehearse=False)      # too few chips
    assert device.describe([Fake()], 1, rehearse=False) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}
