"""Spans and counters of ``repro.obs``: spans nest on their thread, carry
their attributes and scan ids into a CPU profiler trace (a put made on a
transfer-worker thread too), time themselves with or without a profiler
session and keep nothing without one; under a session the registry sums the
spans' seconds by name, counters add up under threads, and the executor
counts plan changes."""
import glob
import sys
import threading
import time
import tracemalloc

import numpy as np
import pytest

from repro import obs
from repro.core import plan as P
from repro.core.compiler import ProgramCache
from repro.core.executor import StreamingExecutor


def _record(tmp_path, fn):
    """Run ``fn`` under a profiler session; the parsed trace."""
    from jax.profiler import ProfileData

    _session(tmp_path, fn)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return ProfileData.from_file(path)


def _session(tmp_path, fn):
    """Run ``fn`` under a profiler session writing to ``tmp_path``."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()


def _growth(before):
    """The registry's growth since the snapshot ``before``."""
    return {k: v - before.get(k, 0) for k, v in obs.snapshot().items()}


def _zipflow_events(pd):
    """[(line index, name, start ns, end ns, stats)] of zipflow.* spans."""
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for i, ln in enumerate(plane.lines):
            for ev in ln.events:
                if ev.name.startswith("zipflow."):
                    out.append((i, ev.name, ev.start_ns, ev.end_ns,
                                dict(ev.stats)))
    return out


def test_spans_nest_on_their_thread_and_carry_attributes(tmp_path):
    def work():
        with obs.root("zipflow.stream", columns=2) as outer:
            with obs.span("zipflow.put", column="A", bytes=128) as inner:
                time.sleep(0.002)
        assert outer.s >= inner.s >= 0.002

    evs = {ev[1]: ev for ev in _zipflow_events(_record(tmp_path, work))}
    line, _, s, e, stats = evs["zipflow.stream"]
    assert stats["columns"] == 2 and isinstance(stats["scan"], int)
    pline, _, ps, pe, pstats = evs["zipflow.put"]
    assert pline == line and s <= ps <= pe <= e
    assert pstats == {"column": "A", "bytes": 128}


def test_a_nested_root_joins_its_scan():
    assert obs.current_scan() is None
    with obs.root("zipflow.query", query="q") as q:
        with obs.root("zipflow.stream", columns=1) as s:
            assert s.scan == q.scan == obs.current_scan()
        assert obs.current_scan() == q.scan
    assert obs.current_scan() is None
    with obs.root("zipflow.query", query="q") as again:
        assert again.scan != q.scan


def test_worker_thread_puts_carry_the_scan_id(tmp_path, rng):
    arr = rng.integers(0, 1000, 20_000).astype(np.int32)
    enc = P.encode(P.make_plan("bitpack"), arr)
    ex = StreamingExecutor(chunk_bytes=4096, async_dispatch=True,
                           cache=ProgramCache())
    out = {}
    pd = _record(tmp_path, lambda: out.update(ex.run({"c": enc})))
    np.testing.assert_array_equal(np.asarray(out["c"].array), arr)
    evs = _zipflow_events(pd)
    (root,) = [ev for ev in evs if ev[1] == "zipflow.stream"]
    puts = [ev for ev in evs if ev[1] == "zipflow.put"]
    assert puts and all(ev[0] != root[0] for ev in puts)
    assert all(ev[4]["scan"] == root[4]["scan"] and ev[4]["column"] == "c"
               for ev in puts)
    assert sum(ev[4]["bytes"] for ev in puts) >= enc.compressed_nbytes
    names = {ev[1] for ev in evs if ev[0] == root[0]}
    assert {"zipflow.plan", "zipflow.stage", "zipflow.launch",
            "zipflow.wait_decode"} <= names
    (launch, *_) = [ev for ev in evs if ev[1] == "zipflow.launch"]
    assert launch[4]["program"] == "decode" and launch[4]["chunk"] == 0


def test_span_without_a_session_times_itself_and_keeps_nothing():
    with obs.span("zipflow.wait_h2d", column="c", chunk=3) as sp:
        time.sleep(0.005)
    assert sp.s >= 0.005
    with obs.span("zipflow.wait_h2d", column="c", chunk=3):
        pass                                    # warm: first-call allocations
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for i in range(20_000):
            with obs.span("zipflow.put", column="c", bytes=i, scan=1):
                pass
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = sum(d.size_diff for d in after.compare_to(before, "filename")
                if d.traceback[0].filename == obs.__file__)
    assert grown < 1024


def test_counters_add_up_under_threads(tmp_path):
    def work():
        threads = [threading.Thread(
            target=lambda: [obs.inc("plan_changes", 3) for _ in range(2000)])
            for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)

    before = obs.snapshot()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        _session(tmp_path, work)
    finally:
        sys.setswitchinterval(interval)
    assert _growth(before)["plan_changes"] == 16 * 2000 * 3
    with pytest.raises(KeyError):
        obs.inc("no_such_counter")


def test_the_registry_records_only_under_a_session(tmp_path):
    def scan():
        with obs.root("zipflow.query", query="q") as q:
            with obs.root("zipflow.stream", columns=1):
                with obs.span("zipflow.wait_h2d", column="c") as wait:
                    time.sleep(0.003)
        obs.inc("plan_changes")
        return q.s, wait.s

    before = obs.snapshot()
    scan()
    assert _growth(before) == dict.fromkeys(before, 0)
    timed = []
    _session(tmp_path, lambda: timed.append(scan()))
    (q_s, wait_s), = timed
    # the nested root adds nothing: its time is in the query's
    grown = {k: v for k, v in _growth(before).items() if v}
    assert grown == pytest.approx({"plan_changes": 1, "zipflow.query": q_s,
                                   "zipflow.wait_h2d": wait_s})


def test_plans_and_plan_changes_are_counted(rng, tmp_path):
    ex = StreamingExecutor(chunk_bytes=None, policy="fifo",
                           cache=ProgramCache())
    for name in ("a", "b"):
        ex.compile(name, P.encode(P.make_plan("bitpack"),
                                  rng.integers(0, 99, 1000).astype(np.int32)))

    def plans():
        ex.plan(["a", "b"])
        ex.plan(["a", "b"])                     # the same plan: no change
        ex.plan(["a"])                          # another column set
        ex.plan(["a", "b"], order=["b", "a"])   # a new order: a change

    before = obs.snapshot()
    _session(tmp_path, plans)
    assert _growth(before)["plan_changes"] == 1
    ex.unregister("b")
    assert all("b" not in cols for cols in ex._last_plans)
