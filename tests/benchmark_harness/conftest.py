"""Fixtures for the benchmark harness's own tests.

`bench_root` builds a throw-away benchmark in a temporary directory: a copy of
`BENCHMARK.json` and `benchmark/`, to which a test adds cells as files, the
way a later PR would, without editing a file that is there. Its cells run at
a scale a test can hold, on the CPU, so nothing they print is a measurement.
"""

import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_helpers import BENCH, REPO  # noqa: E402


@pytest.fixture
def bench_root(tmp_path):
    root = str(tmp_path / "root")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root
