"""TPC-H Q6 (v3.0.1 §2.4.6) at its validation parameters: revenue of the
1994 lineitems with discount in [0.05, 0.07] and quantity < 24."""
from __future__ import annotations

import numpy as np

from bench.tpch.queries.common import blocked_sum, rel_error  # noqa: F401

COLUMNS = ("L_SHIPDATE", "L_DISCOUNT", "L_QUANTITY", "L_EXTENDEDPRICE")
DATE_LO, DATE_HI = 8766, 9131        # [1994-01-01, 1995-01-01)
DISC_LO, DISC_HI = 0.05, 0.07
QTY_LT = 24


def plan():
    from repro.core.query import Bin, Col, Pred, QueryPlan

    return QueryPlan(
        name="q6",
        predicates=(Pred("L_SHIPDATE", ">=", DATE_LO),
                    Pred("L_SHIPDATE", "<", DATE_HI),
                    Pred("L_DISCOUNT", "between", DISC_LO, DISC_HI),
                    Pred("L_QUANTITY", "<", QTY_LT)),
        aggregates=(("revenue", Bin("*", Col("L_EXTENDEDPRICE"),
                                    Col("L_DISCOUNT"))),))


def _selected(c, lo, hi):
    d = c["L_DISCOUNT"]
    return ((c["L_SHIPDATE"] >= DATE_LO) & (c["L_SHIPDATE"] < DATE_HI)
            & (d >= lo) & (d <= hi) & (c["L_QUANTITY"] < QTY_LT))


def reference(c) -> np.ndarray:
    """Float64 revenue.  The discount bounds are float32 constants, as the
    float32 column is compared against them."""
    sel = _selected(c, np.float32(DISC_LO), np.float32(DISC_HI))
    rev = (c["L_EXTENDEDPRICE"][sel].astype(np.float64)
           * c["L_DISCOUNT"][sel].astype(np.float64))
    return np.array([rev.sum()])


def read(result) -> np.ndarray:
    return np.asarray(result, np.float64).reshape(1)


def control(c, dtype) -> np.ndarray:
    """The reference computed in ``dtype`` on the default device, in the
    fused plan's form."""
    import jax.numpy as jnp

    d = jnp.asarray(c["L_DISCOUNT"]).astype(dtype)
    sel = ((jnp.asarray(c["L_SHIPDATE"]) >= DATE_LO)
           & (jnp.asarray(c["L_SHIPDATE"]) < DATE_HI)
           & (d >= jnp.asarray(DISC_LO, dtype)) & (d <= jnp.asarray(DISC_HI, dtype))
           & (jnp.asarray(c["L_QUANTITY"]) < QTY_LT))
    rev = jnp.where(sel, jnp.asarray(c["L_EXTENDEDPRICE"]).astype(dtype) * d,
                    jnp.zeros((), dtype))
    return np.array([float(blocked_sum(rev, dtype))])
