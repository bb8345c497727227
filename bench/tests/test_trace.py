"""Trace reduction, checked on small traces recorded on the CPU and on a
TPU.  The CPU one holds two rounds of two jitted programs inside the
harness's spans, with sleeps that leave the device idle inside
``bench.query.q6`` and ``bench.finalize``."""
from pathlib import Path

import pytest

from bench import trace

TRACE = Path(__file__).parent / "data" / "cpu_trace.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    return trace.reduce(ProfileData.from_file(str(TRACE)),
                        prefix=trace.HOST_PLANE)


def test_union_and_gaps():
    busy = trace.union([(5, 8), (0, 2), (1, 3), (9, 20)], 0, 10)
    assert busy == [(0, 3), (5, 8), (9, 10)]
    assert trace.gaps(busy, 0, 10) == [(3, 5), (8, 9)]
    assert trace.gaps([], 2, 4) == [(2, 4)]


def test_busy_and_idle_of_recorded_trace(reduced):
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["window_s"] == pytest.approx(0.013388543)
    assert reduced["busy_s"] == pytest.approx(0.002159619)
    assert reduced["idle_pct"] == pytest.approx(
        100 * (1 - reduced["busy_s"] / reduced["window_s"]))


def test_breakdown_names_ops_and_the_host_span_of_each_gap(reduced):
    ops = dict(reduced["breakdown"]["device_ops"])
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    assert len(ops) <= trace.TOP_N and len(gaps) <= trace.TOP_N
    assert "jit__lambda:dot_general.1" in ops
    assert sum(ops.values()) <= reduced["window_s"]
    assert gaps["bench.finalize / $time sleep"] > 0.005
    assert gaps["bench.query.q6 / $time sleep"] > 0.003
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])


def test_a_trace_without_device_planes_reduces_to_nothing():
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(TRACE))
    assert trace.reduce(pd) is None           # no /device:TPU: plane


def test_recorded_tpu_trace_names_ops_by_program():
    """A trace recorded on one TPU v5 lite: three calls of one jitted
    program inside ``bench.round``; each op is named module:HLO name."""
    from jax.profiler import ProfileData

    path = Path(__file__).parent / "data" / "tpu_trace.xplane.pb"
    r = trace.reduce(ProfileData.from_file(str(path)))
    assert r["busy_s"] == pytest.approx(0.000390513)
    assert r["window_s"] == pytest.approx(0.00314708)
    assert r["breakdown"]["device_ops"] == [["jit__lambda:%fusion.2",
                                             pytest.approx(0.000390513)]]
    assert {name.split(" / ")[0] for name, _ in
            r["breakdown"]["idle_gaps"]} == {"bench.query.x"}
