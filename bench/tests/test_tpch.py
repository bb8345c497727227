"""The benchmark's TPC-H data and queries: dbgen's flag/status rule, and
fused Q1/Q6 through ``ColumnPipeline.run_query`` against the float64
references."""
import numpy as np
import pytest

from bench.harness import load_module, find
from bench.tpch import gen
from bench.tpch.queries import q1, q6

SEED = 2**31 + 12345        # larger than a signed 32-bit seed


def test_generator_is_seeded_per_column_and_block():
    a = gen.generate(["L_ORDERKEY", "L_SHIPDATE", "L_RETURNFLAG"], 0.002, 3,
                     SEED)
    b = gen.generate(["L_RETURNFLAG"], 0.002, 3, SEED)
    c = gen.generate(["L_ORDERKEY"], 0.002, 3, SEED + 1)
    np.testing.assert_array_equal(a["L_RETURNFLAG"], b["L_RETURNFLAG"])
    assert not np.array_equal(a["L_ORDERKEY"], c["L_ORDERKEY"])
    keys = gen.generate(["L_ORDERKEY", "O_ORDERKEY"], 0.002, 3, SEED)
    assert np.all(np.diff(keys["L_ORDERKEY"].astype(np.int64)) >= 0)
    assert np.all(np.diff(keys["O_ORDERKEY"].astype(np.int64)) > 0)
    np.testing.assert_array_equal(np.unique(keys["L_ORDERKEY"]),
                                  keys["O_ORDERKEY"])
    # no block repeats another
    ship = a["L_SHIPDATE"]
    half = ship.size // 2
    assert ship[:half].sum() * 2 != ship.sum()


def test_order_keys_are_dbgen_sparse():
    """Of every 32 order keys dbgen uses the first 8 (``mk_sparse``), and
    the blocks number their orders on, as one table's would be."""
    keys = gen.generate(["O_ORDERKEY", "L_ORDERKEY"], 0.002, 3, SEED)
    ok = keys["O_ORDERKEY"].astype(np.int64)
    i = np.arange(1, ok.size + 1)
    np.testing.assert_array_equal(ok, (i >> 3) * 32 + (i & 7))
    assert ok[:9].tolist() == [1, 2, 3, 4, 5, 6, 7, 32, 33]
    assert set(np.unique(ok % 32)) == set(range(8))
    lines = np.unique(keys["L_ORDERKEY"], return_counts=True)
    np.testing.assert_array_equal(lines[0], ok)
    assert lines[1].min() == 1 and lines[1].max() == 7


def test_fixed_streams_are_drawn_alike_for_every_seed():
    """The fused cell's configuration fixes what decides L_RETURNFLAG, whose
    ANS stripe is as wide as its longest chunk: every seed makes the same
    flags, and so the same shapes, while the values it sums still follow
    the seed."""
    import json

    from bench.harness import BENCH

    cfg = json.loads((BENCH / "configs/tpch-sf10-q1q6.json").read_text())
    a, b = (gen.generate(["L_RETURNFLAG", "L_LINESTATUS", "L_TAX"], 0.01, 2,
                         s, cfg["fixed_streams"]) for s in (SEED, SEED + 1))
    np.testing.assert_array_equal(a["L_RETURNFLAG"], b["L_RETURNFLAG"])
    np.testing.assert_array_equal(a["L_LINESTATUS"], b["L_LINESTATUS"])
    assert not np.array_equal(a["L_TAX"], b["L_TAX"])
    with pytest.raises(KeyError):
        gen.generate(["L_TAX"], 0.01, 1, SEED, ["no-such-quantity"])


def test_dbgen_flag_and_status_rule():
    c = gen.generate(list(gen.COLUMNS), 0.01, 1, SEED)
    late = c["L_SHIPDATE"] > gen.CURRENTDATE
    np.testing.assert_array_equal(c["L_LINESTATUS"], late.astype(np.int32))
    returned = c["L_RECEIPTDATE"] <= gen.CURRENTDATE
    flags = c["L_RETURNFLAG"]
    assert set(np.unique(flags[~returned])) == {ord("N")}
    assert set(np.unique(flags[returned])) == {ord("A"), ord("R")}
    assert np.all(c["L_RECEIPTDATE"] > c["L_SHIPDATE"])


def test_q1_has_tpch_four_groups():
    c = gen.generate(q1.COLUMNS, 0.01, 1, SEED)
    table = q1.reference(c)
    groups = {(chr(int(f)), int(s)) for f, s in table[:, :2]}
    assert groups == {("A", 0), ("R", 0), ("N", 0), ("N", 1)}


@pytest.mark.parametrize("name", ["q1", "q6"])
def test_fused_query_matches_reference(name):
    from repro.core.plan import encode
    from repro.data.columns import TABLE2_PLANS
    from repro.data.loader import ColumnPipeline

    from bench.store import attach

    mod = load_module(find("query", name))
    cols = gen.generate(mod.COLUMNS, 0.01, 2, SEED)
    pipe = ColumnPipeline({c: TABLE2_PLANS[c] for c in cols},
                          policy="adaptive", chunk_bytes="auto",
                          chunk_decode=True)
    attach(pipe, {c: encode(TABLE2_PLANS[c], a) for c, a in cols.items()})
    got = mod.read(pipe.run_query(mod.plan()).result)
    want = mod.reference(cols)
    assert mod.rel_error(got, want) < 1e-5


def test_rel_error_reads_a_missing_group_as_inf():
    want = np.array([[65.0, 0, 1, 2, 3, 4, 5], [78.0, 1, 1, 2, 3, 4, 5]])
    assert q1.rel_error(want[:1], want) == float("inf")
    assert q1.rel_error(want, want) == 0.0
    assert q6.rel_error([1.0 + 1e-3], [1.0]) == pytest.approx(1e-3)
