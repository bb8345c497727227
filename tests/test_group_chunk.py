"""Group-boundary chunked decode (the CHUNK_GROUP tentpole).

Pins the invariants: (1) group-chunked streaming decode is bitwise-identical to
whole-column decode for Group-Parallel (RLE, DeltaStride) and Non-Parallel (ANS)
graphs, including uneven tail spans and the ANS end-of-stream trim; (2) the
planner's profile mirrors the executor's schedule (planned span counts ==
executed launches) and selects chunk mode for a CHUNK_GROUP graph when the
model favors it; (3) the geometry-tied candidate ladder is actually aligned --
element candidates to kernel tile multiples, group candidates to group-boundary
prefix sums; (4) body/tail span programs are shared across same-structure
columns; (5) cost-model persistence round-trips scales + per-signature timings.
"""
import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan as P
from repro.core.compiler import (ProgramCache, build_graph, compile_decoder,
                                 compile_graph, compile_group_chunk_graph,
                                 compile_group_prologue, device_buffers)
from repro.core.costmodel import (ColumnProfile, CostModel,
                                  aligned_chunk_elems, groups_per_chunk)
from repro.core.executor import StreamingExecutor
from repro.core.geometry import native_subtile
from repro.core.ir import CHUNK_GROUP, group_chunk_layout
from repro.core.patterns import group_ids
from repro.core.planner import CHUNK, plan_execution
from repro.kernels.ref import expand_ref

mp = P.make_plan


def _rle_column(rng, n_groups=500, max_run=120):
    return np.repeat(rng.integers(0, 50, n_groups),
                     rng.integers(1, max_run, n_groups)).astype(np.int32)


# ----------------------------------------------------------- bitwise identity

def test_rle_group_chunk_bitexact(rng):
    """Skewed run lengths + uneven tail span: group-chunked == whole-column."""
    arr = _rle_column(rng, n_groups=501)
    enc = P.encode(mp("rle"), arr)
    whole = StreamingExecutor(chunk_bytes=None, cache=ProgramCache())
    chunked = StreamingExecutor(chunk_bytes=256, chunk_decode=True,
                                cache=ProgramCache())
    chunked.compile("c", enc)
    assert chunked.graph("c").chunkability == CHUNK_GROUP
    sched = chunked.chunk_schedule("c")
    assert sched is not None and sched.kind == "group" and sched.n_chunks > 2
    assert sched.g_sizes[-1] < sched.g_sizes[0]        # uneven tail span
    a = np.asarray(whole.run({"c": enc})["c"].array)
    res = chunked.run({"c": enc})["c"]
    assert res.chunk_decoded and res.decode_launches > 2
    np.testing.assert_array_equal(np.asarray(res.array), a)
    np.testing.assert_array_equal(np.asarray(res.array), arr)


def test_ans_group_chunk_bitexact(rng):
    """ANS chunk-grid spans (stripe column slices): bit-exact incl. the
    end-of-stream trim, for multi-byte and single-byte dtypes."""
    for dtype, n, cb in ((np.int32, 30_000, 4096), (np.uint8, 3_001, 512)):
        arr = rng.integers(0, 40, n).astype(dtype)
        enc = P.encode(P.Plan("ans", params={"chunk_size": 512}), arr)
        ex = StreamingExecutor(chunk_bytes=cb, chunk_decode=True,
                               cache=ProgramCache())
        ex.compile("c", enc)
        assert ex.graph("c").chunkability == CHUNK_GROUP
        res = ex.run({"c": enc})["c"]
        assert res.chunk_decoded and res.decode_launches > 1, dtype
        np.testing.assert_array_equal(np.asarray(res.array), arr)
        np.testing.assert_array_equal(np.asarray(res.array), P.decode_np(enc))


def test_deltastride_group_chunk_bitexact(rng):
    mono = np.arange(80_000, dtype=np.int32)
    mono[17::97] += 3
    enc = P.encode(mp("deltastride"), mono)
    ex = StreamingExecutor(chunk_bytes=2048, chunk_decode=True,
                           cache=ProgramCache())
    res = ex.run({"c": enc})["c"]
    assert res.chunk_decoded and res.decode_launches > 1
    np.testing.assert_array_equal(np.asarray(res.array), mono)


def test_group_chunk_programs_shared_across_columns(rng):
    """Same-structure RLE columns share prologue + body/tail span programs."""
    cache = ProgramCache()
    ex = StreamingExecutor(chunk_bytes=256, chunk_decode=True, cache=cache)
    counts = rng.integers(1, 60, 400)
    # values cycle so no adjacent runs merge: every column has exactly 400
    # groups with the same counts -> identical structure (and signature)
    encs = {f"c{i}": P.encode(mp("rle"),
                              np.repeat((np.arange(400) + i) % 50,
                                        counts).astype(np.int32))
            for i in range(3)}
    results = ex.run(encs)
    for n, enc in encs.items():
        assert results[n].chunk_decoded, n
        np.testing.assert_array_equal(np.asarray(results[n].array),
                                      P.decode_np(enc))
    # whole program (compile) + prologue + body + tail span programs, shared:
    # 3 columns x K spans hit <= 4 cache entries
    assert cache.stats["misses"] <= 4
    assert cache.stats["hits"] >= 2 * (results["c0"].decode_launches - 2)


# ------------------------------------------- group ids without a search

# run lengths of a Group-Parallel stage; zeros are empty groups
GROUP_COUNTS = {
    "one_group": [37],
    "all_size_1": [1] * 64,
    "empty_lead_inner_trail": [0, 0, 3, 0, 0, 5, 1, 0, 2, 0, 0],
    "random_0_7": np.random.default_rng(7).integers(0, 8, 300).tolist(),
}


def _rle_blob(counts) -> P.Encoded:
    """An RLE blob with exactly these run lengths, empty runs kept (the
    encoder emits maximal runs only); every run's value is distinct, so a
    wrong group id shows in the output."""
    counts = np.asarray(counts, np.int32)
    presum = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    values = np.arange(counts.size, dtype=np.int32) * 7 + 3
    return P.Encoded(codec="rle", meta={"n_groups": int(counts.size),
                                        "group_presum": presum},
                     buffers={"values": values, "counts": counts},
                     children={}, n=int(presum[-1]), dtype=np.dtype(np.int32))


def _searched_groups(blob: P.Encoded) -> np.ndarray:
    presum = blob.meta["group_presum"]
    return np.searchsorted(presum, np.arange(blob.n), side="right") - 1


@pytest.mark.parametrize("case", sorted(GROUP_COUNTS))
def test_group_ids_equal_search(case):
    """The prefix sum of run-start marks is the search's group id, and
    expands exactly as the independent oracle does."""
    blob = _rle_blob(GROUP_COUNTS[case])
    presum = jnp.asarray(blob.meta["group_presum"], jnp.int32)
    want = _searched_groups(blob)
    searched = jnp.searchsorted(presum, jnp.arange(blob.n, dtype=jnp.int32),
                                side="right") - 1
    np.testing.assert_array_equal(np.asarray(searched), want)
    np.testing.assert_array_equal(np.asarray(group_ids(presum, blob.n)), want)
    values = blob.buffers["values"]
    np.testing.assert_array_equal(
        np.asarray(expand_ref(presum, jnp.asarray(values), blob.n)),
        values[want])


@pytest.mark.parametrize("backend", ["jnp", "baseline"])
@pytest.mark.parametrize("case", sorted(GROUP_COUNTS))
def test_group_parallel_whole_column_matches_oracle(case, backend):
    """``GroupParallel.run_jnp`` (whole-column decode) equals the search
    oracle and the NumPy decode, empty groups included."""
    blob = _rle_blob(GROUP_COUNTS[case])
    got = np.asarray(compile_decoder(blob, backend=backend)(
        device_buffers(blob)))
    np.testing.assert_array_equal(got, blob.buffers["values"][
        _searched_groups(blob)])
    np.testing.assert_array_equal(got, P.decode_np(blob))


# a column of one group is never group-chunked (``group_chunk_layout``), so
# the span path takes the other counts, and single-group spans besides
@pytest.mark.parametrize("case, body", [
    ("all_size_1", 22), ("empty_lead_inner_trail", 4), ("random_0_7", 101),
    ("random_0_7", 7), ("empty_lead_inner_trail", 1)])
def test_group_chunk_spans_match_oracle(case, body):
    """Span programs over whole groups: body spans padded past their valid
    lanes and an uneven tail span decode the search oracle's values, and
    every padding lane repeats the span's last valid element."""
    blob = _rle_blob(GROUP_COUNTS[case])
    graph = build_graph(blob)
    layout = group_chunk_layout(graph)
    assert set(layout.sliced) == {"root.values"}
    ops = device_buffers(blob)
    resident = compile_group_prologue(graph).fn(ops)
    presum = blob.meta["group_presum"]
    n_groups = len(presum) - 1
    starts = range(0, n_groups, body)
    pad = max(int(presum[min(s + body, n_groups)] - presum[s])
              for s in starts) + 5
    progs = {}
    pieces = []
    for s in starts:
        size = min(body, n_groups - s)
        n_valid = int(presum[s + size] - presum[s])
        pad_elems = pad if size == body else n_valid + 3
        if (size, pad_elems) not in progs:
            progs[size, pad_elems] = compile_group_chunk_graph(
                graph, size, pad_elems)
        prog = progs[size, pad_elems]
        bufs = {**ops, **resident,
                "root.values": ops["root.values"][s:s + size]}
        out = np.asarray(prog(bufs, np.int32(presum[s]), np.int32(s),
                              np.int32(n_valid)))
        assert out.shape == (pad_elems,) and n_valid < pad_elems
        if n_valid:
            assert (out[n_valid:] == out[n_valid - 1]).all()
        pieces.append(out[:n_valid])
    assert size < body or body == 1              # an uneven tail span
    got = np.concatenate(pieces)
    np.testing.assert_array_equal(got, blob.buffers["values"][
        _searched_groups(blob)])
    np.testing.assert_array_equal(got, P.decode_np(blob))


def _sparse_keys(rng):
    """dbgen-style sparse order keys (the first 8 of every 32) and their
    lineitem repeats of 1-7: the stream's key columns in miniature."""
    k = np.arange(3_000)
    okey = ((k // 8) * 32 + k % 8 + 1).astype(np.int32)
    return okey, np.repeat(okey, rng.integers(1, 8, okey.size))


# plan, which of ``_sparse_keys``' columns it encodes, and whether the
# executor group-chunks it (RLE over a Group-Parallel child decodes whole)
GP_PLANS = {
    "rle": (mp("rle"), 1, True),
    "deltastride": (mp("deltastride"), 0, True),
    "rle_deltastride": (P.Plan("rle", children={"values": mp("deltastride")}),
                        1, False),
}


@pytest.mark.parametrize("name", sorted(GP_PLANS))
def test_group_parallel_codecs_bitexact(name, rng):
    """RLE, DeltaStride and RLE{DeltaStride} decode bit-exact against the
    NumPy decode, whole-column (jnp and baseline) and group-chunked where
    the executor chunks the plan."""
    plan, which, chunked = GP_PLANS[name]
    arr = _sparse_keys(rng)[which]
    enc = P.encode(plan, arr)
    for backend in ("jnp", "baseline"):
        got = compile_decoder(enc, backend=backend)(device_buffers(enc))
        np.testing.assert_array_equal(np.asarray(got), P.decode_np(enc),
                                      err_msg=backend)
    ex = StreamingExecutor(chunk_bytes=1024, chunk_decode=True,
                           cache=ProgramCache())
    res = ex.run({"c": enc})["c"]
    assert res.chunk_decoded == chunked
    assert res.decode_launches > 2 if chunked else res.decode_launches == 1
    np.testing.assert_array_equal(np.asarray(res.array), P.decode_np(enc))
    np.testing.assert_array_equal(np.asarray(res.array), arr)


def _has_while(lowered) -> bool:
    return re.search(r"\bwhile\b", lowered.as_text()) is not None


@pytest.mark.parametrize("name", sorted(GP_PLANS))
def test_group_parallel_programs_have_no_search_loop(name, rng):
    """The whole-column decode programs of the Group-Parallel codecs hold no
    loop: a binary search for group ids (a ``while`` of log2(n_groups)
    levels, each gathering once per output) cannot come back unnoticed."""
    plan, which, _ = GP_PLANS[name]
    enc = P.encode(plan, _sparse_keys(rng)[which])
    prog = compile_graph(build_graph(enc), backend="jnp")
    args = {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
            for k, v in P.host_operands(enc).items()}
    assert not _has_while(prog.fn.lower(args))
    # the check sees a search where there is one
    presum = jax.ShapeDtypeStruct((enc.meta["n_groups"] + 1,), jnp.int32)
    assert _has_while(jax.jit(lambda p: jnp.searchsorted(
        p, jnp.arange(enc.n), side="right")).lower(presum))


# ------------------------------------------------------------ planner mirror

def test_planner_mirrors_executor_span_counts(rng):
    """Profile-predicted span counts == executed decode launches (minus the
    one-shot prologue), through a real plan round trip."""
    arr = _rle_column(rng, n_groups=800)
    ans = rng.integers(0, 40, 60_000).astype(np.int32)
    ex = StreamingExecutor(chunk_bytes="auto", chunk_decode=True,
                           policy="adaptive", cache=ProgramCache())
    ex.compile("rle", P.encode(mp("rle"), arr))
    ex.compile("ans", P.encode(P.Plan("ans", params={"chunk_size": 1024}), ans))
    # inject measurements WITHOUT calibration (scales stay 1.0) so the modeled
    # launch overhead is the raw chip estimate and overlap wins
    ex.cost_model.measured["rle"] = (0.05, 0.05)
    ex.cost_model.measured["ans"] = (0.04, 0.06)
    ep = ex.plan()
    assert ep.decisions["rle"].decode_mode == CHUNK
    assert ep.decisions["ans"].decode_mode == CHUNK
    assert ep.modeled_makespan_s <= min(ep.baselines.values()) + 1e-9
    res = ex.run(plan=ep)
    for n, extra in (("rle", 1), ("ans", 0)):       # rle has a presum prologue
        d, r = ep.decisions[n], res[n]
        assert r.chunk_decoded, n
        assert r.decode_launches == d.n_chunks + extra, n
    np.testing.assert_array_equal(np.asarray(res["rle"].array), arr)
    np.testing.assert_array_equal(np.asarray(res["ans"].array), ans)


def test_chunk_decision_carries_uneven_weights(rng):
    """Group decisions model per-chunk byte counts, not uniform splits: the
    whole-resident bytes land ahead of span 0 and decode follows the
    group-boundary prefix sums."""
    arr = _rle_column(rng, n_groups=600)
    ex = StreamingExecutor(chunk_bytes=512, chunk_decode=True,
                           policy="chunk-johnson", cache=ProgramCache())
    ex.compile("rle", P.encode(mp("rle"), arr))
    ex.cost_model.measured["rle"] = (0.05, 0.05)
    ep = ex.plan()
    d = ep.decisions["rle"]
    assert d.decode_mode == CHUNK and len(d.weights) == d.n_chunks
    t, dws = zip(*d.weights)
    assert t[0] > t[1]                      # span 0 carries the resident bytes
    assert abs(sum(t) - 1.0) < 1e-9 and abs(sum(dws) - 1.0) < 1e-9
    sched = ex.chunk_schedule("rle", d.chunk_bytes)
    np.testing.assert_allclose(
        dws, np.asarray(sched.out_sizes) / sum(sched.out_sizes), rtol=1e-9)


# ----------------------------------------------------------- geometry ladder

def test_geometry_ladder_is_aligned():
    """Element candidates snap to kernel tile multiples, group candidates to
    group-boundary (alignment-multiple) spans -- under the same shared formulas
    the executor slices with."""
    cm = CostModel()
    tile = native_subtile("fp", cm.spec.name)
    elem_p = ColumnProfile(
        name="e", compressed_nbytes=1 << 22, plain_nbytes=1 << 24, n_kernels=1,
        signature="sig-e", leaves=((1 << 20, 1 << 22),), chunkable=True,
        n_out=1 << 22, per_elem_bytes=1.0, align=32)
    ladder = cm.chunk_ladder(elem_p)
    assert ladder, "element ladder must not be empty"
    for cb in ladder:
        elems = aligned_chunk_elems(cb, elem_p.per_elem_bytes, elem_p.align)
        assert elems % tile == 0 and elems % elem_p.align == 0, (cb, elems)
    presum = np.arange(0, 4097 * 7, 7, dtype=np.int64)
    group_p = ColumnProfile(
        name="g", compressed_nbytes=1 << 16, plain_nbytes=1 << 20, n_kernels=2,
        signature="sig-g", leaves=((4096, 1 << 16),), group_chunkable=True,
        n_out=int(presum[-1]), n_groups=4096, group_bytes=4.0, group_align=8,
        pattern="gp", group_out_presum=presum)
    gladder = cm.chunk_ladder(group_p)
    assert gladder, "group ladder must not be empty"
    for cb in gladder:
        g = groups_per_chunk(cb, group_p.group_bytes, group_p.group_align)
        assert g % group_p.group_align == 0 and g < group_p.n_groups, (cb, g)


def test_ladder_prunes_overhead_dominated_candidates():
    """After calibration inflates the launch-overhead estimate, tiny candidates
    (per-chunk decode < 2x overhead) drop off the ladder."""
    cm = CostModel()
    p = ColumnProfile(
        name="e", compressed_nbytes=1 << 20, plain_nbytes=1 << 22, n_kernels=4,
        signature="s", leaves=((1 << 18, 1 << 20),), chunkable=True,
        n_out=1 << 20, per_elem_bytes=1.0, align=8)
    cm.register(p)
    full = cm.chunk_ladder(p)
    cm.observe("e", 0.1, 0.1)               # decode_scale explodes (CPU-like)
    pruned = cm.chunk_ladder(p)
    assert len(pruned) <= len(full)
    assert min(pruned) >= min(full)


# ------------------------------------------------------------- persistence

def test_cost_model_save_load_roundtrip(rng, tmp_path):
    """A fresh process (new CostModel) plans from persisted history: scales and
    per-signature timing summaries survive; predictions for a same-structure
    column match the stored means."""
    arr = _rle_column(rng, n_groups=300)
    enc = P.encode(mp("rle"), arr)
    ex = StreamingExecutor(chunk_bytes=None, cache=ProgramCache())
    ex.run({"c": enc})
    cm = ex.cost_model
    path = str(tmp_path / "cost.json")
    cm.save(path)
    with open(path) as f:
        data = json.load(f)
    assert data["n_observed"] >= 1 and data["signatures"]

    cm2 = CostModel.load(path)
    assert cm2.n_observed == cm.n_observed
    assert cm2.transfer_scale == pytest.approx(cm.transfer_scale)
    assert cm2.decode_scale == pytest.approx(cm.decode_scale)
    # a fresh executor over the SAME structure predicts the persisted means
    ex2 = StreamingExecutor(chunk_bytes=None, cache=ProgramCache(),
                            cost_model=cm2)
    ex2.compile("fresh", P.encode(mp("rle"), arr))
    sig = ex2.graph("fresh").signature
    assert sig in cm2.sig_stats
    t, d = cm2.predict("fresh")
    assert t == pytest.approx(cm2.sig_stats[sig]["transfer_s"])
    assert d == pytest.approx(cm2.sig_stats[sig]["decode_s"])
    # and jobs() stays in consistent wall-clock units without re-measuring
    jobs = cm2.jobs(["fresh"])
    assert jobs[0].transfer_s == pytest.approx(t)


def test_plan_survives_forced_whole_mode(rng):
    """Forcing whole decode through the plan bypasses group chunking."""
    arr = _rle_column(rng, n_groups=400)
    enc = P.encode(mp("rle"), arr)
    ex = StreamingExecutor(chunk_bytes=256, chunk_decode=True,
                           cache=ProgramCache())
    ex.compile("c", enc)
    ep = ex.plan()
    from repro.core.planner import WHOLE
    whole = dataclasses.replace(
        ep, decisions={n: dataclasses.replace(d, decode_mode=WHOLE)
                       for n, d in ep.decisions.items()})
    res = ex.run({"c": enc}, plan=whole)["c"]
    assert not res.chunk_decoded and res.decode_launches == 1
    np.testing.assert_array_equal(np.asarray(res.array), arr)


def test_tpch_group_columns_bitexact_under_auto_plan():
    """TPC-H: every column decodes bit-identically under the adaptive auto
    plan, and the ANS column (L_RETURNFLAG) is group-chunkable."""
    from repro.data.columns import TABLE2_PLANS
    from repro.data.loader import ColumnPipeline
    from repro.data.tpch import generate

    cols = generate(scale=0.002, seed=5)
    names = ["L_RETURNFLAG", "L_ORDERKEY", "L_QUANTITY"]
    pipe = ColumnPipeline({n: TABLE2_PLANS[n] for n in names},
                          chunk_bytes="auto", chunk_decode=True,
                          policy="adaptive")
    pipe.compress({n: cols[n] for n in names})
    assert pipe.executor.graph("L_RETURNFLAG").chunkability == CHUNK_GROUP
    assert group_chunk_layout(pipe.executor.graph("L_RETURNFLAG")) is not None
    results = pipe.run()
    for n in names:
        np.testing.assert_array_equal(np.asarray(results[n].array), cols[n],
                                      err_msg=n)
    ep = pipe.plan()
    assert ep.modeled_makespan_s <= min(ep.baselines.values()) + 1e-9
    # force the group-streamed path on the ANS column (span = one group) and
    # compare bit-for-bit against the whole-column result
    enc = P.encode(TABLE2_PLANS["L_RETURNFLAG"], cols["L_RETURNFLAG"])
    ex = StreamingExecutor(chunk_bytes=256, chunk_decode=True,
                           cache=ProgramCache())
    res = ex.run({"c": enc})["c"]
    assert res.chunk_decoded and res.decode_launches > 1
    np.testing.assert_array_equal(np.asarray(res.array), cols["L_RETURNFLAG"])
