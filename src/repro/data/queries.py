"""Mini analytical query engine (the TQP role): JAX scan-filter-aggregate
implementations of TPC-H Q1 and Q6 used by the end-to-end benchmarks/examples,
``q1_numpy`` / ``q6_numpy``, their float64 NumPy references, and ``ANSWERS``,
which reads an engine's result in its reference's form.

``Q1_PLAN`` / ``Q6_PLAN`` are the same queries as declarative ``QueryPlan`` IR
(``core.query``): ``lower_query`` grafts them onto the columns' decode graphs
so scan-filter-aggregate runs inside the per-chunk decode launch and only
partial aggregates ever reach HBM (late materialization)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.query import (Bin, Col, Const, Pred, QueryPlan,
                              blocked_segment_sum)


# Q1 groups by (L_RETURNFLAG, L_LINESTATUS).  The flag is the raw character
# 'A', 'N' or 'R'; (flag - 65) % 5 gives the three flags the distinct codes
# 0, 3 and 2, and a row's segment is code * 2 + L_LINESTATUS (0 or 1).
Q1_SEGMENTS = 8
_Q1_FLAG_OF_CODE = {0: ord("A"), 2: ord("R"), 3: ord("N")}


def q1_engine(c):
    """TPC-H Q1: filtered group-by aggregates over lineitem, as (5, 8)
    lanes x segments (read them as the answer table with ``q1_answer``)."""
    sel = c["L_SHIPDATE"] <= jnp.int32(10000)
    flag = (c["L_RETURNFLAG"].astype(jnp.int32) - 65) % 5
    key = flag * 2 + c["L_LINESTATUS"]
    disc_price = c["L_EXTENDEDPRICE"] * (1 - c["L_DISCOUNT"])
    charge = disc_price * (1 + c["L_TAX"])
    w = sel.astype(jnp.float32)
    lanes = [v * w for v in (c["L_QUANTITY"].astype(jnp.float32),
                             c["L_EXTENDEDPRICE"], disc_price, charge)] + [w]
    return blocked_segment_sum(lanes, key, Q1_SEGMENTS).T


def q6_engine(c):
    """TPC-H Q6: predicated revenue sum."""
    sel = ((c["L_SHIPDATE"] >= 8766) & (c["L_SHIPDATE"] < 9131)
           & (c["L_DISCOUNT"] >= 0.05) & (c["L_DISCOUNT"] <= 0.07)
           & (c["L_QUANTITY"] < 24))
    rev = jnp.where(sel, c["L_EXTENDEDPRICE"] * c["L_DISCOUNT"], 0.0)
    return blocked_segment_sum([rev])[0]


def q1_numpy(c) -> np.ndarray:
    """Float64 NumPy reference for TPC-H Q1 as its answer table: one row per
    (L_RETURNFLAG, L_LINESTATUS) pair among the selected rows, in the query's
    ORDER BY order, with columns returnflag, linestatus, sum_qty,
    sum_base_price, sum_disc_price, sum_charge, count_order.  The groups are
    the pairs of values themselves, not the engines' segment codes."""
    sel = c["L_SHIPDATE"] <= 10000
    flag = c["L_RETURNFLAG"][sel].astype(np.int64)
    status = c["L_LINESTATUS"][sel].astype(np.int64)
    pairs, group = np.unique(flag * 2**32 + status, return_inverse=True)
    price = c["L_EXTENDEDPRICE"][sel].astype(np.float64)
    disc_price = price * (1 - c["L_DISCOUNT"][sel].astype(np.float64))
    charge = disc_price * (1 + c["L_TAX"][sel].astype(np.float64))
    lanes = (c["L_QUANTITY"][sel].astype(np.float64), price, disc_price,
             charge, np.ones(price.shape))
    sums = [np.bincount(group, weights=v, minlength=pairs.size) for v in lanes]
    return np.stack([pairs // 2**32, pairs % 2**32, *sums], axis=1)


def q1_answer(out) -> np.ndarray:
    """An engine's (5, 8) Q1 lanes as the answer table ``q1_numpy`` gives:
    one row per segment that counted rows, named by its (flag, status)."""
    out = np.asarray(out, np.float64)
    rows = []
    for seg in np.flatnonzero(out[4]):          # the count lane
        if seg // 2 not in _Q1_FLAG_OF_CODE:
            raise ValueError(f"Q1 rows in segment {seg}, which no flag has")
        rows.append([_Q1_FLAG_OF_CODE[seg // 2], seg % 2, *out[:, seg]])
    return np.array(sorted(rows), np.float64).reshape(-1, 7)


def q6_numpy(c) -> float:
    """Float64 NumPy reference for ``q6_engine``.  The discount bounds are
    float32 constants, as the engine compares them against float32 values."""
    d = c["L_DISCOUNT"]
    sel = ((c["L_SHIPDATE"] >= 8766) & (c["L_SHIPDATE"] < 9131)
           & (d >= np.float32(0.05)) & (d <= np.float32(0.07))
           & (c["L_QUANTITY"] < 24))
    return float(np.sum(c["L_EXTENDEDPRICE"][sel].astype(np.float64)
                        * d[sel].astype(np.float64)))


def q6_answer(out) -> float:
    return float(np.asarray(out))


ENGINES = {1: q1_engine, 6: q6_engine}
NUMPY_REFERENCES = {1: q1_numpy, 6: q6_numpy}
# an engine's or a fused plan's result in its reference's form
ANSWERS = {1: q1_answer, 6: q6_answer}


# --------------------------------------------------- declarative QueryPlan IR

_DISC_PRICE = Bin("*", Col("L_EXTENDEDPRICE"),
                  Bin("-", Const(1), Col("L_DISCOUNT")))

# lane order matches q1_engine: quantity, extendedprice, disc_price, charge,
# and the always-computed count lane doubles as the engine's ``w`` lane
Q1_PLAN = QueryPlan(
    name="q1",
    predicates=(Pred("L_SHIPDATE", "<=", 10000),),
    aggregates=(
        ("sum_qty", Col("L_QUANTITY", "float32")),
        ("sum_base_price", Col("L_EXTENDEDPRICE")),
        ("sum_disc_price", _DISC_PRICE),
        ("sum_charge", Bin("*", _DISC_PRICE,
                           Bin("+", Const(1), Col("L_TAX")))),
    ),
    group_key=Bin("+", Bin("*", Bin("%", Bin("-", Col("L_RETURNFLAG", "int32"),
                                             Const(65)),
                                   Const(5)),
                           Const(2)),
                  Col("L_LINESTATUS")),
    n_segments=Q1_SEGMENTS,
    keep_count_lane=True)

Q6_PLAN = QueryPlan(
    name="q6",
    predicates=(Pred("L_SHIPDATE", ">=", 8766),
                Pred("L_SHIPDATE", "<", 9131),
                Pred("L_DISCOUNT", "between", 0.05, 0.07),
                Pred("L_QUANTITY", "<", 24)),
    aggregates=(("revenue", Bin("*", Col("L_EXTENDEDPRICE"),
                                Col("L_DISCOUNT"))),))

QUERY_PLANS = {1: Q1_PLAN, 6: Q6_PLAN}
