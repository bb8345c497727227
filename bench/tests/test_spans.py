"""The per-layer metrics that read the program's registry (``bench/spans.py``
over ``repro.obs``): the arithmetic on a registry recorded under a profiler
session, a real stream through the executor, and a program without the
registry."""
import sys
import time
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import harness, spans

METRICS = ("plan_ms_per_scan", "plan_changes_per_scan",
           "issue_host_ms_per_scan", "h2d_wait_ms_per_scan")


def _read(name, run):
    return harness.load_module(harness.find("metric", name)).read(run)


def _window(tmp_path, monkeypatch, fn):
    """Run ``fn`` under a profiler session, as a traced window; the
    registry then reads what grew in it."""
    import jax
    from repro import obs

    before = obs.snapshot()
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    grown = {k: v - before.get(k, 0) for k, v in obs.snapshot().items()}
    monkeypatch.setattr(spans, "registry", lambda: grown)
    return grown


def test_readers_over_a_recorded_window(tmp_path, monkeypatch):
    """Two scans: each root sleeps 4 ms in its own time, 3 ms in
    ``zipflow.wait_h2d``, 2 ms in ``zipflow.wait_decode`` and 1 ms in
    ``zipflow.plan``."""
    from repro import obs

    timed = []

    def scan():
        with obs.root("zipflow.query", query="q") as root:
            with obs.root("zipflow.stream", columns=1):
                with obs.span("zipflow.plan", policy="fifo") as plan:
                    time.sleep(0.001)
                with obs.span("zipflow.wait_h2d", column="c") as h2d:
                    time.sleep(0.003)
                with obs.span("zipflow.wait_decode", column="c") as dec:
                    time.sleep(0.002)
                time.sleep(0.004)
        obs.inc("plan_changes")
        timed.append((root.s, plan.s, h2d.s, dec.s))

    _window(tmp_path, monkeypatch, lambda: [scan() for _ in range(2)])
    root_s, plan_s, h2d_s, dec_s = (sum(t) for t in zip(*timed))
    run = NS(scans=2)
    got = {name: _read(name, run) for name in METRICS}
    assert got == pytest.approx({
        "plan_ms_per_scan": 1e3 * plan_s / 2,
        "plan_changes_per_scan": 1.0,
        "issue_host_ms_per_scan": 1e3 * (root_s - plan_s - h2d_s - dec_s) / 2,
        "h2d_wait_ms_per_scan": 1e3 * h2d_s / 2})
    assert got["issue_host_ms_per_scan"] >= 4.0
    assert got["h2d_wait_ms_per_scan"] >= 3.0


def test_a_stream_through_the_executor(tmp_path, monkeypatch):
    """Chunked transfers through the worker thread: the waits lie inside
    the stream's root, so the issue time is its root's time less them."""
    from repro.core import plan as P
    from repro.core.compiler import ProgramCache
    from repro.core.executor import StreamingExecutor

    arr = np.random.default_rng(0).integers(0, 1000, 20_000).astype(np.int32)
    enc = P.encode(P.make_plan("bitpack"), arr)
    ex = StreamingExecutor(chunk_bytes=4096, async_dispatch=True,
                           cache=ProgramCache())
    ex.run({"c": enc})                          # compiles outside the window
    out = {}
    grown = _window(tmp_path, monkeypatch,
                    lambda: out.update(ex.run({"c": enc})))
    np.testing.assert_array_equal(np.asarray(out["c"].array), arr)
    assert grown["zipflow.plan"] > 0 and grown["zipflow.wait_decode"] > 0
    run = NS(scans=1)
    issue = _read("issue_host_ms_per_scan", run)
    assert 0 < issue < 1e3 * grown["zipflow.stream"]
    assert _read("plan_changes_per_scan", run) == 0


def test_no_registry_or_no_scan_reads_nothing(monkeypatch):
    """A program from before ``repro.obs``, as the benchmark also runs:
    every reader leaves its metric out, and so does an empty window."""
    import repro

    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(repro, "obs")
    assert spans.registry() is None
    assert all(_read(name, NS(scans=3)) is None for name in METRICS)
    monkeypatch.undo()
    assert spans.registry() is not None
    assert all(_read(name, NS(scans=0)) is None for name in METRICS)
