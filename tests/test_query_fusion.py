"""Decode-fused query execution (codec x operator fusion, late materialization):
Q1/Q6 fused == reference engines, per-chunk partial aggregates across uneven
tails, RLE per-run aggregation, Eq.-2 traffic delta, planner fused-vs-
materialize decisions, and the finite in-flight window in ``simulate_stream``."""
import numpy as np
import pytest

from repro.core import plan as P, scheduler
from repro.core.compiler import ProgramCache
from repro.core.ir import query_chunk_layout
from repro.core.fusion import hbm_traffic_bytes
from repro.core.query import (Bin, Col, Const, Pred, QueryPlan,
                              blocked_segment_sum, lower_query)
from repro.data.columns import TABLE2_PLANS
from repro.data.loader import ColumnPipeline
from repro.data.queries import (ANSWERS, ENGINES, NUMPY_REFERENCES, Q1_PLAN,
                                Q6_PLAN, q1_engine, q6_engine)
from repro.data.tpch import QUERY_COLUMNS, generate, scale_columns

mp = P.make_plan


def _tpch(scale=0.002, factor=1):
    cols = generate(scale=scale, seed=0)
    if factor > 1:
        cols = scale_columns(cols, factor,
                             [n for n in cols if n.startswith("L_")])
    return cols


def _encode(cols, names):
    return {n: P.encode(TABLE2_PLANS[n], cols[n]) for n in names}


def _run_whole(fq, res=None):
    """Evaluate the fused graph in ONE chunk launch spanning all items."""
    import jax.numpy as jnp

    from repro.core.compiler import compile_query_chunk_graph

    n_items = fq.graph.stages[-1].n_in      # the terminal Reduce's item axis
    prog = compile_query_chunk_graph(fq.graph, n_items)
    bufs = {k: jnp.asarray(v) for k, v in fq.operands.items()}
    if res:
        bufs.update({k: jnp.asarray(v) for k, v in res.items()})
    return prog(bufs, 0)


# ------------------------------------------------ accumulation / references

@pytest.mark.parametrize("n", [1, 1000, 4096, 10_001])
def test_blocked_segment_sum_matches_float64(n, rng):
    import jax.numpy as jnp

    lanes = [(rng.random(n) * 1e5).astype(np.float32) for _ in range(3)]
    key = rng.integers(-1, 9, n).astype(np.int32)  # -1 and 8 drop, as in
    ok = (key >= 0) & (key < 8)                    # jax.ops.segment_sum
    got = np.asarray(blocked_segment_sum([jnp.asarray(v) for v in lanes],
                                         jnp.asarray(key), 8))
    want = np.stack([np.bincount(key[ok], weights=v[ok].astype(np.float64),
                                 minlength=8) for v in lanes], axis=1)
    assert got.shape == (8, 3)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    total = np.asarray(blocked_segment_sum([jnp.asarray(v) for v in lanes]))
    np.testing.assert_allclose(total, [v.astype(np.float64).sum()
                                       for v in lanes], rtol=1e-5)


@pytest.mark.parametrize("q", [1, 6])
def test_engines_match_float64_reference(q):
    cols = _tpch(scale=0.01)
    c = {n: cols[n] for n in QUERY_COLUMNS[q]}
    ref = NUMPY_REFERENCES[q](c)
    # the generator draws every (flag, status) pair: six Q1 groups
    assert np.shape(ref) == ((6, 7) if q == 1 else ())
    np.testing.assert_allclose(ANSWERS[q](ENGINES[q](c)), ref, rtol=1e-5)


@pytest.mark.parametrize("path", ["engine", "fused"])
def test_q1_real_groups_stay_distinct(path):
    """TPC-H Q1's four groups (A,F) (N,F) (N,O) (R,F) come out as four rows,
    each with its own rows' sums: no two flags share a segment."""
    groups = [(b"A", 0), (b"N", 0), (b"N", 1), (b"R", 0)]   # F = 0, O = 1
    per = [100, 200, 300, 400]                               # rows per group
    n = sum(per)
    cols = {
        "L_RETURNFLAG": np.concatenate([np.full(k, g[0][0], np.uint8)
                                        for g, k in zip(groups, per)]),
        "L_LINESTATUS": np.concatenate([np.full(k, g[1], np.int32)
                                        for g, k in zip(groups, per)]),
        "L_QUANTITY": np.arange(n, dtype=np.int32) % 50 + 1,
        "L_EXTENDEDPRICE": (np.arange(n) % 977 + 900).astype(np.float32),
        "L_DISCOUNT": (np.arange(n) % 11 / 100).astype(np.float32),
        "L_TAX": (np.arange(n) % 9 / 100).astype(np.float32),
        "L_SHIPDATE": np.full(n, 9000, np.int32),
    }
    if path == "engine":
        out = q1_engine(cols)
    else:
        pipe = ColumnPipeline({c: TABLE2_PLANS[c] for c in cols},
                              chunk_bytes=1 << 12, chunk_decode=True)
        pipe.compress(cols)
        out = pipe.run_query(Q1_PLAN).result
    got = ANSWERS[1](out)
    ref = NUMPY_REFERENCES[1](cols)
    assert [tuple(r) for r in got[:, :2]] == [(g[0][0], g[1]) for g in groups]
    assert got[:, -1].tolist() == per
    np.testing.assert_allclose(got, ref, rtol=1e-5)


# ------------------------------------------------------ fused == engine

def test_q6_fused_matches_engine_bitwise_count():
    cols = _tpch()
    encs = _encode(cols, QUERY_COLUMNS[6])
    fq = lower_query(Q6_PLAN, encs)
    assert not fq.resident          # all four Q6 columns fuse
    acc = np.asarray(_run_whole(fq))
    ref = float(q6_engine({k: np.asarray(v) for k, v in
                           ((n, cols[n]) for n in QUERY_COLUMNS[6])}))
    np.testing.assert_allclose(float(fq.finalize(acc)), ref, rtol=1e-5)
    # integer-predicate count lane is exact: recompute the mask in numpy
    # (float32 constants keep the discount comparison in float32, like jax)
    d = cols["L_DISCOUNT"]
    m = ((cols["L_SHIPDATE"] >= 8766) & (cols["L_SHIPDATE"] < 9131)
         & (d >= np.float32(0.05)) & (d <= np.float32(0.07))
         & (cols["L_QUANTITY"] < 24))
    assert fq.selected_rows(acc) == float(m.sum())


def test_q1_fused_matches_engine():
    cols = _tpch()
    encs = _encode(cols, QUERY_COLUMNS[1])
    fq = lower_query(Q1_PLAN, encs)
    assert "L_RETURNFLAG" in fq.resident      # ANS column gathers decoded
    # resident input comes from the normal decode path
    acc = np.asarray(_run_whole(
        fq, {fq.resident_input("L_RETURNFLAG"): cols["L_RETURNFLAG"]}))
    out = np.asarray(fq.finalize(acc))
    ref = np.asarray(q1_engine({n: np.asarray(cols[n])
                                for n in QUERY_COLUMNS[1]}))
    np.testing.assert_allclose(out, ref, rtol=1e-4)


# ------------------------------------------------- chunked partial aggregates

@pytest.mark.parametrize("chunk_bytes", [1 << 12, 1 << 14])
def test_q6_chunked_uneven_tail(chunk_bytes):
    """Per-chunk partial aggregates over row spans with an uneven tail sum to
    the whole-graph answer bitwise."""
    cols = _tpch()
    names = QUERY_COLUMNS[6]
    pipe = ColumnPipeline({n: TABLE2_PLANS[n] for n in names},
                          chunk_bytes=chunk_bytes, chunk_decode=True)
    pipe.compress({n: cols[n] for n in names})
    qe = pipe.run_query(Q6_PLAN)
    assert qe.n_chunks > 1          # the tail chunk is a different trace size
    ref = float(q6_engine({n: np.asarray(cols[n]) for n in names}))
    np.testing.assert_allclose(float(np.asarray(qe.result)), ref, rtol=1e-5)
    # late materialization: only partial-aggregate lanes reached HBM
    assert qe.traffic_bytes < qe.prefuse_traffic_bytes
    assert qe.plain_bytes > 0


def test_q1_chunked_resident_gather():
    cols = _tpch()
    names = QUERY_COLUMNS[1]
    pipe = ColumnPipeline({n: TABLE2_PLANS[n] for n in names},
                          chunk_bytes=1 << 14, chunk_decode=True)
    pipe.compress({n: cols[n] for n in names})
    qe = pipe.run_query(Q1_PLAN)
    assert "L_RETURNFLAG" in qe.resident
    ref = np.asarray(q1_engine({n: np.asarray(cols[n]) for n in names}))
    np.testing.assert_allclose(np.asarray(qe.result), ref, rtol=1e-4)


def test_fused_graph_never_materializes_rows():
    """No fused stage writes a row-count-sized output: the column never
    exists in HBM."""
    cols = _tpch()
    encs = _encode(cols, QUERY_COLUMNS[6])
    fq = lower_query(Q6_PLAN, encs)
    n = fq.n_rows
    for st in fq.graph.stages:
        assert getattr(st, "n_out", 0) != n, st.name
    layout = query_chunk_layout(fq.graph)
    assert layout is not None and layout.tiled


# ------------------------------------------------------- RLE per-run path

def test_rle_per_run_aggregation():
    """Predicated sum over an RLE column aggregates per RUN (run-length
    weighted), never expanding to the row axis."""
    from repro.algos.rle import run_reduce_graph

    rng = np.random.default_rng(3)
    counts = rng.integers(1, 60, 400)
    values = np.cumsum(rng.integers(1, 4, 400)).astype(np.int32)
    arr = np.repeat(values, counts).astype(np.int32)
    enc = P.encode(P.Plan("rle", children={"counts": mp("bitpack"),
                                           "values": mp("bitpack")}), arr)
    lo = int(np.quantile(values, 0.3))
    g = run_reduce_graph(enc, lambda v: v >= lo, [lambda v: v * 2],
                         digest="t")
    from repro.core.compiler import compile_query_chunk_graph, device_buffers

    prog = compile_query_chunk_graph(g, g.stages[-1].n_in)
    acc = np.asarray(prog(device_buffers(enc), 0))
    m = arr >= lo
    np.testing.assert_allclose(acc[0], float((arr[m] * 2).sum()), rtol=1e-6)
    assert acc[1] == float(m.sum())
    # run-granular: no stage output is row-count sized
    assert all(getattr(st, "n_out", 0) != enc.n for st in g.stages)


# -------------------------------------------------------- traffic + planner

def test_operator_fusion_traffic_delta():
    cols = _tpch()
    encs = _encode(cols, QUERY_COLUMNS[6])
    fq = lower_query(Q6_PLAN, encs)
    pre = hbm_traffic_bytes(fq.prefuse_stages, fq.operands)
    post = hbm_traffic_bytes(fq.graph.stages, fq.operands)
    plain = sum(e.plain_nbytes for e in encs.values())
    assert post < pre               # fusion removed round-trips
    assert pre - post >= plain      # at least the decoded columns' bytes


def test_plan_explain_reports_fused():
    cols = _tpch()
    names = QUERY_COLUMNS[6]
    pipe = ColumnPipeline({n: TABLE2_PLANS[n] for n in names},
                          chunk_bytes=1 << 14, chunk_decode=True)
    pipe.compress({n: cols[n] for n in names})
    pipe.run()                      # measured timings calibrate the model
    qe = pipe.run_query(Q6_PLAN)    # observed selectivity feeds the EWMA
    ep = pipe.query_plan(Q6_PLAN)
    fused = [n for n, d in ep.decisions.items() if d.fused]
    assert fused                    # low selectivity: fusing must win somewhere
    text = ep.explain()
    assert "+fused" in text and "sel=" in text
    for n in fused:
        sel = ep.decisions[n].selectivity
        np.testing.assert_allclose(sel, qe.selectivity, atol=1e-6)


def test_selectivity_ewma_learns():
    from repro.core.costmodel import DEFAULT_SELECTIVITY

    cols = _tpch()
    names = QUERY_COLUMNS[6]
    pipe = ColumnPipeline({n: TABLE2_PLANS[n] for n in names},
                          chunk_bytes=1 << 14, chunk_decode=True)
    pipe.compress({n: cols[n] for n in names})
    cm = pipe.executor.cost_model
    qe = pipe.run_query(Q6_PLAN)
    assert qe.selectivity < DEFAULT_SELECTIVITY / 2     # Q6 is selective
    for c in names:
        if c in cm.profiles:
            assert cm.selectivity_for(c) != DEFAULT_SELECTIVITY


# ------------------------------------------------- finite in-flight window

def _jobs():
    jobs = [scheduler.Job(f"c{i}", 0.004, 0.006) for i in range(3)]
    infos = [scheduler.ChunkInfo(n_chunks=8, chunk_decode=True,
                                 launch_overhead_s=1e-4) for _ in range(3)]
    return jobs, infos, list(range(3))


def test_simulate_stream_window_none_unchanged():
    jobs, infos, order = _jobs()
    base = scheduler.simulate_stream(jobs, infos, order)
    assert scheduler.simulate_stream(jobs, infos, order, window=None) == base
    # a huge window is the same as unbounded
    assert scheduler.simulate_stream(jobs, infos, order, window=1000) == base


def test_simulate_stream_window_monotone():
    jobs, infos, order = _jobs()
    base = scheduler.simulate_stream(jobs, infos, order)
    prev = None
    for w in (1, 2, 4, 8):
        t = scheduler.simulate_stream(jobs, infos, order, window=w)
        assert t >= base - 1e-12          # a bound can only slow things down
        if prev is not None:
            assert t <= prev + 1e-12      # wider window never hurts
        prev = t


def test_plan_window_is_cost_driven():
    cols = _tpch()
    names = QUERY_COLUMNS[6]
    pipe = ColumnPipeline({n: TABLE2_PLANS[n] for n in names},
                          chunk_bytes=1 << 14, chunk_decode=True,
                          policy="adaptive")
    pipe.compress({n: cols[n] for n in names})
    pipe.run()
    ep = pipe.plan()
    assert 2 <= ep.window <= 8
    # the chosen window's simulated makespan matches the unbounded plan
    jobs = [scheduler.Job(n, *pipe.executor.timings[n]) for n in ep.order]
    infos = [scheduler.ChunkInfo(
        n_chunks=ep.decisions[n].n_chunks,
        chunk_decode=ep.decisions[n].decode_mode == "chunk",
        launch_overhead_s=pipe.executor.cost_model.launch_overhead_s(n))
        for n in ep.order]
    bound = scheduler.simulate_stream(jobs, infos, window=ep.window)
    free = scheduler.simulate_stream(jobs, infos)
    assert bound <= free * (1 + 1e-6)


# ------------------------------------------------------------ ad-hoc query

def test_adhoc_between_projection():
    """A hand-built QueryPlan (not Q1/Q6) lowers and runs end to end."""
    rng = np.random.default_rng(1)
    n = 6000
    a = rng.integers(0, 100, n).astype(np.int32)
    b = (rng.integers(0, 500, n) / 100.0).astype(np.float32)
    plans = {"A": mp("bitpack"),
             "B": P.Plan("float2int", children={"ints": mp("bitpack")})}
    qp = QueryPlan(name="adhoc",
                   predicates=(Pred("A", "between", 10, 60),),
                   aggregates=(("s", Bin("*", Col("B"), Const(3.0))),))
    pipe = ColumnPipeline(plans, chunk_bytes=1 << 12, chunk_decode=True)
    pipe.compress({"A": a, "B": b})
    qe = pipe.run_query(qp)
    m = (a >= 10) & (a <= 60)
    b2 = np.round(b, 2).astype(np.float32)      # float2int quantizes
    np.testing.assert_allclose(float(np.asarray(qe.result)),
                               float((b2[m] * 3.0).sum()), rtol=1e-5)
