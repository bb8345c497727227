"""Each cell through the one command, end to end on the CPU at the
rehearsal scale."""
import json

import pytest

from bench import harness

ROOT = harness.ROOT


@pytest.mark.parametrize("workload,trace", [
    ("sf10-q1q6.fused", "0"), ("sf10-q1q6.fused", "1"),
    ("sf10-q12cols.stream", "0"), ("sf10-q12cols.stream", "1")])
def test_cell_runs_end_to_end_on_cpu(workload, trace, store_dir, bench_cmd):
    p = bench_cmd("--workload", workload, "--seed", "3000000001",
                  "--seconds", "1", "--trace", trace, "--rehearse",
                  "--store-dir", str(store_dir))
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] >= 1
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    kind = "per_layer" if trace == "1" else "end_to_end"
    expected = {m["name"] for m in spec[kind]
                if workload in m.get("workloads", [workload])}
    # device numbers need a TPU trace or memory stats: absent on the CPU
    cpu_absent = {"peak_hbm_gb", "device_idle_pct", "decode_roofline_pct"}
    assert set(res["metrics"]) == expected - cpu_absent
    for name, check in res["checks"].items():
        assert check["value"] <= check["limit"], name
    tail = p.stderr.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") for line in tail)
