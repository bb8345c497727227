"""Ahead-of-time compiles of the main path's programs for a TPU v5e chip.

Nothing runs: JAX's TPU compiler compiles each program for a v5e that is
described, not attached, and refuses what the chip's compiler would refuse.
The programs are the ``jnp`` decode program of every Q1/Q6 Table-2 column and
the decode-fused Q1/Q6 query chunk programs, at TPC-H SF 1 row counts; each
must fit one chip's 16 GB, and fused Q1 must decode each column once however
many aggregate lanes read it.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import plan as P
from repro.core.compiler import (build_graph, compile_graph,
                                 compile_query_chunk_graph)
from repro.core.query import Bin, Col, QueryPlan, lower_query
from repro.data.columns import TABLE2_PLANS
from repro.data.queries import Q1_PLAN, QUERY_PLANS
from repro.data.tpch import QUERY_COLUMNS, generate, scale_columns

HBM_BYTES = 16 * 10**9          # one v5e chip
COLUMNS = sorted(set(QUERY_COLUMNS[1]) | set(QUERY_COLUMNS[6]))


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs on disk
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def encs():
    """Table-2 encodings of the Q1/Q6 columns at SF 1 row counts (SF 0.1
    tiled ten times: the shapes the chip compiles for, without generating
    the other tables)."""
    cols = generate(scale=0.1, seed=0)
    cols = scale_columns({n: cols[n] for n in COLUMNS}, 10)
    return {n: P.encode(TABLE2_PLANS[n], cols[n]) for n in COLUMNS}


def _shape(a, sharding) -> jax.ShapeDtypeStruct:
    a = np.asarray(a)
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


def _assert_fits(compiled) -> None:
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    assert used <= HBM_BYTES, f"{used / 1e9:.2f} GB > one chip's HBM"


@pytest.mark.parametrize("col", COLUMNS)
def test_decode_program_compiles_for_v5e(col, encs, one_chip):
    enc = encs[col]
    prog = compile_graph(build_graph(enc), backend="jnp", chip="v5e")
    args = {k: _shape(v, one_chip) for k, v in P.host_operands(enc).items()}
    _assert_fits(prog.fn.lower(args).compile())


@pytest.fixture(scope="module")
def fused_program(encs, one_chip):
    """Compile a query plan's single-chunk program once per module."""
    done = {}

    def compiled(qplan: QueryPlan):
        if qplan.name not in done:
            fq = lower_query(qplan, {c: encs[c] for c in qplan.columns()})
            args = {k: _shape(v, one_chip) for k, v in fq.operands.items()}
            for c in fq.resident:   # non-fusible columns ride decoded, whole
                args[fq.resident_input(c)] = jax.ShapeDtypeStruct(
                    (fq.n_rows,), np.dtype(encs[c].dtype), sharding=one_chip)
            prog = compile_query_chunk_graph(fq.graph, fq.n_rows)
            start = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
            done[qplan.name] = prog.fn.lower(args, start).compile()
        return done[qplan.name]
    return compiled


@pytest.mark.parametrize("q", [1, 6])
def test_fused_query_program_compiles_for_v5e(q, fused_program):
    _assert_fits(fused_program(QUERY_PLANS[q]))


def test_fused_q1_decodes_each_column_once(fused_program):
    """Q1 reduces five lanes, four of which read decoded columns.  Its
    program holds as many gathers (the bit-unpacking word reads, the
    float2int fraction-table reads) as a plan with one lane over the same
    columns: a decode chain re-run per lane would multiply them."""
    one_lane = QueryPlan(
        name="q1_one_lane", predicates=Q1_PLAN.predicates,
        aggregates=(("all", Bin("*", Col("L_QUANTITY", "float32"),
                                Q1_PLAN.aggregates[3][1])),),
        group_key=Q1_PLAN.group_key, n_segments=Q1_PLAN.n_segments,
        keep_count_lane=True)
    assert set(one_lane.columns()) == set(Q1_PLAN.columns())

    def gathers(compiled) -> int:
        return len(re.findall(r" gather\(", compiled.as_text()))

    assert gathers(fused_program(Q1_PLAN)) == gathers(fused_program(one_lane))
