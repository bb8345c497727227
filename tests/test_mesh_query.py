"""Row-sharded fused queries (``ColumnPipeline(mesh=N).run_query``).

In-process tests need one device: the per-chip chunk search of
``planner.query_chunk_bytes``, a shard decoded in one span when its plan says
one chunk, and the mesh chunk program over a one-chip mesh, whose padding
rows the Reduce does not count.

The four-chip paths need four jax devices, and XLA's host-device count is
locked at first init, so they run in a subprocess with forced host devices
(the pattern of tests/test_mesh_decode.py).  On TPC-H Q1 and Q6, at a row
count divisible neither by 4 nor by the chunk size, each case checks the
row-sharded answer against the one-chip ``run_query`` answer and the float64
NumPy references within the benchmark configuration's limits, the
``mesh_rows`` counter against the row count, that a second query compiles
nothing, and the programs compiled: the chunk programs once for the mesh, one
combine, and the resident column's shard program once per chip.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import costmodel, planner
from repro.core import plan as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ chunk search

def test_query_chunk_bytes_is_one_chunk_on_a_serial_host():
    """On the CPU transfer and decode share the cores: chunking only buys
    launch overhead, on one chip and on four."""
    for scales in ((1.0,), (1.0, 1.0, 1.0, 1.0)):
        assert planner.query_chunk_bytes("q", 10_000, 1.0, 1.0, 1e-3, 2,
                                         link_scales=scales) is None


def test_query_chunk_bytes_per_chip(monkeypatch):
    """Where transfer and decode overlap, the ladder's largest count wins on
    cheap launches; on N links each chip streams 1/N of the bytes, so the
    chunk is a chip's share over the count."""
    monkeypatch.setattr(costmodel, "serial_host", lambda: False)
    k = planner.QUERY_CHUNK_LADDER[-1]
    one = planner.query_chunk_bytes("q", 10_001, 1.0, 1.0, 1e-6, 2)
    assert one == -(-10_001 // k)
    four = planner.query_chunk_bytes("q", 10_001, 1.0, 1.0, 1e-6, 2,
                                     link_scales=(1.0,) * 4)
    assert four == -(-(-(-10_001 // 4)) // k)
    # launches dearer than any overlap can win back: one chunk
    assert planner.query_chunk_bytes("q", 10_001, 1.0, 1.0, 10.0, 2,
                                     link_scales=(1.0,) * 4) is None


def test_query_chunk_bytes_priced_by_the_slowest_link(monkeypatch):
    """A count is priced by its slowest chip: a 6x link makes the job
    transfer-bound there, which no uniform topology shows."""
    monkeypatch.setattr(costmodel, "serial_host", lambda: False)
    calls = []
    orig = planner.scheduler.simulate_stream

    def spy(jobs, infos, window=None, **kw):
        calls.append(jobs[0].transfer_s)
        return orig(jobs, infos, window=window, **kw)

    monkeypatch.setattr(planner.scheduler, "simulate_stream", spy)
    planner.query_chunk_bytes("q", 4096, 8.0, 1.0, 1e-6, 2,
                              link_scales=(6.0, 1.0, 1.0, 1.0))
    assert max(calls) == pytest.approx(8.0 * 6.0 / 4)
    assert min(calls) == pytest.approx(8.0 / 4)


# ------------------------------------------------------------ shard spans

def test_shard_without_chunk_bytes_decodes_in_one_span():
    """A shard whose plan decision is one chunk runs as one span, as the
    planner priced it; a chunk size still cuts it into many."""
    from repro.core.compiler import ProgramCache
    from repro.core.executor import StreamingExecutor

    rng = np.random.default_rng(5)
    arr = rng.integers(60, 90, 40_000).astype(np.uint8)
    enc = P.encode(P.Plan("ans", params={"chunk_size": 512}), arr)
    ex = StreamingExecutor(chunk_bytes=None, cache=ProgramCache())
    ex.compile("c", enc)
    one = ex.shard_schedule("c", None, 10, 40)
    assert one.n_chunks == 1 and one.g_sizes == (30,)
    many = ex.shard_schedule("c", 1 << 12, 10, 40)
    assert many.n_chunks > 1 and sum(many.g_sizes) == 30


# ------------------------------------------------------ mesh chunk program

@pytest.mark.parametrize("q", [1, 6])
def test_mesh_program_skips_padding_rows(q):
    """The mesh chunk program on a one-chip mesh: a partition padded with
    zero words past its rows, told ``n_valid``, gives the unpadded one-chip
    answer; told the padded length, it counts the padding too."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.compiler import (MESH_AXIS, compile_mesh_query_chunk,
                                     compile_query_chunk_graph, mesh_of)
    from repro.core.ir import query_chunk_layout
    from repro.core.query import lower_query, mesh_lanes
    from repro.data.columns import TABLE2_PLANS
    from repro.data.queries import QUERY_PLANS
    from repro.data.tpch import generate

    cols = generate(scale=0.002, seed=4)
    qp = QUERY_PLANS[q]
    encs = {c: P.encode(TABLE2_PLANS[c], cols[c]) for c in qp.columns()}
    fq = lower_query(qp, encs)
    n = fq.n_rows
    layout = query_chunk_layout(fq.graph)
    pad_rows = -(-(n + 100) // layout.align) * layout.align
    res = {fq.resident_input(c): jnp.asarray(cols[c]) for c in fq.resident}
    ref = compile_query_chunk_graph(fq.graph, n)(
        {**{k: jnp.asarray(v) for k, v in fq.operands.items()}, **res}, 0)

    chips = jax.devices()[:1]
    mesh = mesh_of(chips)
    rows = NamedSharding(mesh, PartitionSpec(MESH_AXIS))
    rep = NamedSharding(mesh, PartitionSpec())
    tiled = {}
    for nm, spec in layout.tiled.items():
        num = (int(fq.operands[spec.num_op][0]) if spec.num_op
               else int(spec.num))
        words = pad_rows * num // spec.den
        src = np.asarray(fq.operands[nm])
        padded = np.zeros(words, src.dtype)
        padded[:min(words, src.size)] = src[:words]
        tiled[nm] = jax.device_put(padded, rows)
    for c in fq.resident:
        padded = np.zeros(pad_rows, cols[c].dtype)
        padded[:n] = cols[c]
        tiled[fq.resident_input(c)] = jax.device_put(padded, rows)
    whole = {nm: jax.device_put(np.asarray(fq.operands[nm]), rep)
             for nm in layout.whole}
    lanes = mesh_lanes(fq.graph.n_out)
    prog = compile_mesh_query_chunk(fq.graph, pad_rows, chips, lanes)

    def run(n_valid):
        acc = jax.device_put(np.zeros(lanes, np.float32), rows)
        nv = jax.device_put(np.array([n_valid], np.int32), rows)
        out = np.asarray(prog(tiled, whole, np.int32(0), nv, acc))
        assert not out[fq.graph.n_out:].any()
        return out[:fq.graph.n_out]

    np.testing.assert_allclose(run(n), np.asarray(ref), rtol=1e-6)
    counted = fq.selected_rows(run(pad_rows))
    assert counted > fq.selected_rows(np.asarray(ref)) or q == 6


# ----------------------------------------------- four chips (subprocess)

_SUBPROC = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import tempfile
import jax
import numpy as np
from repro import obs
from repro.core.compiler import ProgramCache
from repro.core.costmodel import LinkTopology
from repro.data.columns import TABLE2_PLANS
from repro.data.loader import ColumnPipeline
from repro.data.queries import ANSWERS, NUMPY_REFERENCES, QUERY_PLANS
from repro.data.tpch import generate

assert jax.device_count() == 4
SCENARIO = sys.argv[1]
LIMITS = {1: 3e-5, 6: 1e-5}      # bench/configs/tpch-sf10-q1q6-rows4.json
N_ROWS = 50_003                  # not a multiple of 4, of 4096, nor of 32
names = ["L_RETURNFLAG", "L_LINESTATUS", "L_QUANTITY", "L_EXTENDEDPRICE",
         "L_DISCOUNT", "L_TAX", "L_SHIPDATE"]
cols = {c: a[:N_ROWS] for c, a in generate(scale=0.01, seed=3).items()
        if c in names}
assert all(a.size == N_ROWS for a in cols.values())
compiles = [0]
jax.monitoring.register_event_duration_secs_listener(
    lambda ev, d, **kw: compiles.__setitem__(
        0, compiles[0] + (ev == "/jax/core/compile/backend_compile_duration")))
# skewed: a 6x-slow host link 0 and a cheap fabric, so the planner lands
# resident shards off their chips and D2D legs carry them back; a fixed
# chunk size cuts every chip's partition into many chunks
chunk_bytes = 1 << 13 if SCENARIO == "skewed" else None


def pipeline(mesh):
    pipe = ColumnPipeline({c: TABLE2_PLANS[c] for c in names},
                          policy="adaptive", chunk_bytes=chunk_bytes,
                          chunk_decode=True, mesh=mesh,
                          placement="sharded" if mesh else None)
    pipe.executor.cache = ProgramCache()
    pipe.compress(cols)
    if mesh and SCENARIO == "skewed":
        pipe.executor.cost_model.topology = LinkTopology(
            n_links=4, link_scale=(6.0, 1.0, 1.0, 1.0), d2d_scale=0.05)
    return pipe


def rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (got.shape, want.shape)
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-9)))


def first_rounds(pipe):
    c0 = compiles[0]
    out = {q: pipe.run_query(QUERY_PLANS[q]) for q in (6, 1)}
    return out, compiles[0] - c0


one = pipeline(None)
one_out, one_compiles = first_rounds(one)
mesh = pipeline(4)
tmp = tempfile.mkdtemp()
jax.profiler.start_trace(tmp)       # counters count under a profiler session
mesh_out, mesh_compiles = first_rounds(mesh)
jax.profiler.stop_trace()
reg = obs.snapshot()
assert reg["mesh_rows"] == 2 * N_ROWS, reg       # each query's rows, once
assert reg["mesh_chips"] == 2 * 4, reg
assert reg["mesh_rows_full"] >= reg["mesh_rows"], reg
assert reg.get("zipflow.combine", 0) > 0, reg

for q in (6, 1):
    ref = NUMPY_REFERENCES[q](cols)
    got = ANSWERS[q](mesh_out[q].result)
    e_ref = rel_err(got, ref)
    e_one = rel_err(got, ANSWERS[q](one_out[q].result))
    assert e_ref <= LIMITS[q] and e_one <= LIMITS[q], (q, e_ref, e_one)
    qe = mesh_out[q]
    assert sorted(qe.device_launches) == [d.id for d in jax.devices()], qe
    assert all(k >= qe.n_chunks for k in qe.device_launches.values()), qe

res = mesh_out[1].resident["L_RETURNFLAG"]
assert res.shard_devices == (0, 1, 2, 3), res.shard_devices
rplan = [p for _, p in mesh.executor._mesh_plans.values() if p is not None]
assert len(rplan) == 1
if SCENARIO == "skewed":
    assert rplan[0].redistribution, "the slow link should move a shard"
    assert mesh_out[6].n_chunks > 1 and mesh_out[1].n_chunks > 1
    assert reg.get("zipflow.d2d", 0) > 0, reg
else:
    assert not rplan[0].redistribution
    # one chunk program per query for the whole mesh, one combine for
    # both queries, and the resident shards' span program on each chip in
    # place of the one-chip path's whole-column decode
    assert mesh_compiles == one_compiles + 1 + (4 - 1), (mesh_compiles,
                                                         one_compiles)

kinds = lambda pipe, kind: sum(1 for k in pipe.executor.cache._programs
                               if kind in k)
# a chip's partition pads to a whole number of chunks: no tail program
assert kinds(mesh, "mqchunk") <= kinds(one, "qchunk"), (
    list(mesh.executor.cache._programs), list(one.executor.cache._programs))
assert kinds(mesh, "combine") == 1

c0 = compiles[0]
again = {q: mesh.run_query(QUERY_PLANS[q]) for q in (6, 1)}
assert compiles[0] == c0, "a second round compiled"
for q in (6, 1):
    assert rel_err(ANSWERS[q](again[q].result),
                   NUMPY_REFERENCES[q](cols)) <= LIMITS[q]
print("MESH_QUERY_OK", SCENARIO, one_compiles, mesh_compiles)
"""


@pytest.mark.parametrize("scenario", ["placed", "skewed"])
def test_row_sharded_query_subprocess(scenario):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SUBPROC, scenario], env=env,
                         capture_output=True, text=True, timeout=600)
    assert f"MESH_QUERY_OK {scenario}" in out.stdout, \
        out.stdout + "\n" + out.stderr[-3000:]
