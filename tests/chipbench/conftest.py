"""tests/chipbench: the benchmark's own tests (CPU, toy sizes). The
parent conftest pins the CPU and puts the repo root on sys.path."""
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture
def bench_copy(tmp_path):
    """A directory that holds only BENCHMARK.json and chipbench/: what a
    later PR edits by adding files, and where the command must refuse
    to run (the program is not there)."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path
