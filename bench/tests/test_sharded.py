"""The four-chip cell through the one command on the CPU at the rehearsal
scale, on four forced host devices (XLA's device count is fixed when JAX
starts, so each run is a process of its own): its end-to-end and per-layer
metrics, and ``correct`` false when one chip's partial aggregates are left
out of the combine or when the control takes the program's place."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT
CELL = "sf10-q1q6.sharded4"
SEED = 3000000011

_RUN = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[0:0] = [ROOT, os.path.join(ROOT, "src")]
import jax
import numpy as np
from bench import harness, readings


def leave_out_a_chip(pipe):
    # the last chip's accumulator lanes are zeroed before the combine
    cache = pipe.executor.cache
    orig = cache.get_combine

    def get_combine(devices, lanes):
        combine = orig(devices, lanes)

        def dropped(acc):
            a = np.asarray(acc).copy()
            a[-lanes:] = 0
            return combine(jax.device_put(a, acc.sharding))
        return dropped
    cache.get_combine = get_combine


argv = ["--workload", CELL, "--seed", SEED, "--seconds", "0.2", "--trace",
        TRACE, "--rehearse", "--store-dir", STORE]
hooks = None
if FAULT == "chip":
    hooks = leave_out_a_chip
elif FAULT == "control":
    cell = harness.load_cell(harness.ROOT / "BENCHMARK.json", CELL, False)
    hooks = readings.control_hook(
        cell, harness.source_columns(cell.config, int(SEED), rehearse=True))
sys.exit(harness.main(argv, hooks=hooks))
"""


def run_cell(store_dir, trace="0", fault=""):
    code = (f"ROOT, CELL, SEED = {str(ROOT)!r}, {CELL!r}, {str(SEED)!r}\n"
            f"TRACE, FAULT, STORE = {trace!r}, {fault!r}, "
            f"{str(store_dir)!r}\n" + _RUN)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", ["0", "1"])
def test_cell_runs_on_four_devices(trace, store_dir):
    res = run_cell(store_dir, trace)
    assert res["correct"] is True and res["failed"] == 0, res["checks"]
    assert res["device"]["count"] == 4
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    kind = "per_layer" if trace == "1" else "end_to_end"
    expected = {m["name"] for m in spec[kind]
                if CELL in m.get("workloads", [CELL])}
    # device numbers need a TPU trace or memory stats: absent on the CPU
    cpu_absent = {"peak_hbm_gb", "mesh_roofline_pct"}
    assert set(res["metrics"]) == expected - cpu_absent
    if trace == "1":
        assert res["metrics"]["combine_ms_per_scan"]["value"] > 0
        assert 0 <= res["metrics"]["mesh_row_skew_pct"]["value"] < 100


@pytest.mark.parametrize("fault", ["chip", "control"])
def test_a_broken_sharded_path_is_not_correct(fault, store_dir):
    res = run_cell(store_dir, fault=fault)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values()), \
        res["checks"]
