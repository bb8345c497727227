"""The command around the cells: the store read on a second run, the
refusal of a non-TPU platform without ``--rehearse`` or without the
program, and discovery of files by name."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT


def test_second_run_reads_the_store(store_dir, bench_cmd):
    args = ("--workload", "sf10-q1q6.fused", "--seed", "41", "--seconds",
            "0.1", "--trace", "0", "--rehearse", "--store-dir",
            str(store_dir))
    first, second = bench_cmd(*args), bench_cmd(*args)
    assert first.returncode == 0 and second.returncode == 0
    assert "store: miss" in first.stderr and "encoded" in first.stderr
    assert "(not set-up)" in first.stderr
    assert "store: hit" in second.stderr and "no encoding" in second.stderr
    assert "encoded" not in second.stderr


def test_non_tpu_platform_without_rehearse_exits_nonzero(store_dir,
                                                          bench_cmd):
    p = bench_cmd("--workload", "sf10-q1q6.fused", "--seed", "1",
                  "--seconds", "1", "--trace", "0", "--store-dir",
                  str(store_dir))
    assert p.returncode != 0
    assert p.stdout == ""


def test_only_the_benchmark_files_is_not_enough(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ cannot import the
    program: the command fails and prints no result."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".store", ".jax_cache",
                                                  "__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sf10-q1q6.fused",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse",
         "--store-dir", str(tmp_path / "store")],
        cwd=tmp_path, env={**os.environ, "JAX_PLATFORMS": "cpu",
                           "PYTHONPATH": ""},
        capture_output=True, text=True, timeout=600)
    assert p.returncode != 0
    assert p.stdout == ""


def test_files_added_under_their_directory_are_found_by_name(tmp_path):
    base = tmp_path / "bench"
    (base / "configs").mkdir(parents=True)
    (base / "traffic").mkdir()
    (base / "metrics").mkdir()
    cfg = {"name": "new-config", "columns": ["L_TAX"], "limits": {}}
    (base / "configs" / "new-config.json").write_text(json.dumps(cfg))
    (base / "traffic" / "new-mix.json").write_text(
        json.dumps({"unit": "stream", "loop": "closed", "clients": 1}))
    (base / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return run.scans * 2\n")
    spec = {"configs": [{"name": "new-config",
                         "file": "bench/configs/new-config.json"}],
            "workloads": [{"name": "new.cell", "config": "new-config",
                           "traffic": "new-mix", "chips": 1}],
            "end_to_end": [{"name": "new_metric", "unit": "count"}],
            "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell(tmp_path / "BENCHMARK.json", "new.cell", False,
                             base=base)
    assert cell.config == cfg and cell.traffic["unit"] == "stream"
    assert list(cell.metrics) == ["new_metric"]
    reader = harness.load_module(harness.find("metric", "new_metric", base))
    assert reader.read(harness.Run(scans=3)) == 6
    with pytest.raises(FileNotFoundError):
        harness.find("traffic", "no-such-mix", base)
