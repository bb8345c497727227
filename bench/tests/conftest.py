"""Shared fixtures of the benchmark's tests: one store directory per
session, so each (configuration, seed) is encoded once, and the command."""
import os
import subprocess
import sys

import pytest

from bench.harness import ROOT


@pytest.fixture(scope="session")
def store_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("bench") / "store"


@pytest.fixture
def bench_cmd():
    """Runs ``bench/run.py`` with arguments on the CPU; the finished
    process."""
    def run(*args):
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        return subprocess.run([sys.executable, "bench/run.py", *args],
                              cwd=ROOT, env=env, capture_output=True,
                              text=True, timeout=600)
    return run
