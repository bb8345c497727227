"""TPC-H Q1 (v3.0.1 §2.4.1) at its validation parameter (DELTA = 90 days):
per (L_RETURNFLAG, L_LINESTATUS) sums over lineitems shipped by 1998-09-02.

An answer is a table with one row per group in ORDER BY order and the
columns returnflag, linestatus, sum_qty, sum_base_price, sum_disc_price,
sum_charge, count_order.  The averages are ratios of these sums and add
nothing to compare.
"""
from __future__ import annotations

import numpy as np

from bench.tpch.queries.common import blocked_sum, rel_error  # noqa: F401

COLUMNS = ("L_RETURNFLAG", "L_LINESTATUS", "L_QUANTITY", "L_EXTENDEDPRICE",
           "L_DISCOUNT", "L_TAX", "L_SHIPDATE")
SHIPDATE_MAX = 10471                 # 1998-12-01 - 90 days
SEGMENTS = 8
# the plan's segment is ((flag - 65) % 5) * 2 + status: 'A', 'R', 'N' have
# the codes 0, 2 and 3
_FLAG_OF_CODE = {0: ord("A"), 2: ord("R"), 3: ord("N")}


def plan():
    from repro.core.query import Bin, Col, Const, Pred, QueryPlan

    disc_price = Bin("*", Col("L_EXTENDEDPRICE"),
                     Bin("-", Const(1), Col("L_DISCOUNT")))
    return QueryPlan(
        name="q1",
        predicates=(Pred("L_SHIPDATE", "<=", SHIPDATE_MAX),),
        aggregates=(
            ("sum_qty", Col("L_QUANTITY", "float32")),
            ("sum_base_price", Col("L_EXTENDEDPRICE")),
            ("sum_disc_price", disc_price),
            ("sum_charge", Bin("*", disc_price,
                               Bin("+", Const(1), Col("L_TAX")))),
        ),
        group_key=Bin("+", Bin("*", Bin("%", Bin("-", Col("L_RETURNFLAG",
                                                          "int32"),
                                                 Const(65)),
                                        Const(5)),
                               Const(2)),
                      Col("L_LINESTATUS")),
        n_segments=SEGMENTS,
        keep_count_lane=True)


def reference(c) -> np.ndarray:
    sel = c["L_SHIPDATE"] <= SHIPDATE_MAX
    key = (c["L_RETURNFLAG"][sel].astype(np.int64) * 2
           + c["L_LINESTATUS"][sel].astype(np.int64))
    price = c["L_EXTENDEDPRICE"][sel].astype(np.float64)
    disc_price = price * (1 - c["L_DISCOUNT"][sel].astype(np.float64))
    charge = disc_price * (1 + c["L_TAX"][sel].astype(np.float64))
    lanes = (c["L_QUANTITY"][sel].astype(np.float64), price, disc_price,
             charge, np.ones(price.shape))
    sums = [np.bincount(key, weights=v, minlength=512) for v in lanes]
    # rows (flag, status, *sums) for the groups that counted rows, sorted
    rows = [[k // 2, k % 2, *(s[k] for s in sums)]
            for k in np.flatnonzero(sums[-1])]
    return np.array(sorted(rows), np.float64).reshape(-1, 7)


def read(result) -> np.ndarray:
    """The fused plan's (5, SEGMENTS) lanes as the reference's table."""
    out = np.asarray(result, np.float64)
    rows = []
    for seg in np.flatnonzero(out[4]):
        if seg // 2 not in _FLAG_OF_CODE:       # a flag no TPC-H row has
            return np.full((0, 7), np.nan)
        rows.append([_FLAG_OF_CODE[seg // 2], seg % 2, *out[:, seg]])
    return np.array(sorted(rows), np.float64).reshape(-1, 7)


def control(c, dtype) -> np.ndarray:
    """The reference computed in ``dtype`` on the default device, as the
    fused plan's (5, SEGMENTS) lanes, so ``read`` reads it as it reads the
    program's answer."""
    import jax.numpy as jnp

    sel = jnp.asarray(c["L_SHIPDATE"]) <= SHIPDATE_MAX
    flag = jnp.asarray(c["L_RETURNFLAG"]).astype(jnp.int32)
    seg = ((flag - 65) % 5) * 2 + jnp.asarray(c["L_LINESTATUS"])
    price = jnp.asarray(c["L_EXTENDEDPRICE"]).astype(dtype)
    one = jnp.ones((), dtype)
    disc_price = price * (one - jnp.asarray(c["L_DISCOUNT"]).astype(dtype))
    charge = disc_price * (one + jnp.asarray(c["L_TAX"]).astype(dtype))
    lanes = (jnp.asarray(c["L_QUANTITY"]).astype(dtype), price, disc_price,
             charge, jnp.ones(price.shape, dtype))
    out = np.zeros((len(lanes), SEGMENTS))
    for k in np.unique(np.asarray(seg)):
        hit = sel & (seg == int(k))
        for lane, v in enumerate(lanes):
            out[lane, k] = float(blocked_sum(
                jnp.where(hit, v, jnp.zeros((), dtype)), dtype))
    return out
