"""TPC-H LINEITEM and ORDERS columns, made from a seed.

The benchmark's own copy of the program's generator (``repro.data.tpch``),
cut to the columns a configuration reads, and closer to dbgen (TPC-H v3.0.1
§4.2.3) where the copy departed from it:

* dates are days since 1970-01-01: O_ORDERDATE uniform in
  [STARTDATE, ENDDATE - 151], L_SHIPDATE = O_ORDERDATE + [1, 121],
  L_COMMITDATE = O_ORDERDATE + [30, 90], L_RECEIPTDATE = L_SHIPDATE + [1, 30];
* L_LINESTATUS is 'O' (1) if L_SHIPDATE is after CURRENTDATE (1995-06-17),
  else 'F' (0);
* L_RETURNFLAG is 'N' if L_RECEIPTDATE is after CURRENTDATE, else 'R' or 'A'
  with equal odds, so Q1 has TPC-H's four groups;
* L_EXTENDEDPRICE = L_QUANTITY * P_RETAILPRICE(L_PARTKEY).

Categorical strings are int32 codes (L_LINESTATUS, L_SHIPMODE) and
L_RETURNFLAG is its raw character, as the program's Table-2 plans expect.

O_ORDERKEY is dbgen's sparse key (``mk_sparse``): of every 32 keys the
first 8 are used, so the i-th order (from 1) has the key
``(i >> 3) << 5 | (i & 7)``, up to SF x 6 M; L_ORDERKEY repeats it once per
lineitem.

A table is made in blocks of ``block_scale`` (SF 1 = 1.5 M orders, ~6 M
lineitems), each drawn from its own streams of (seed, block, quantity), and
numbering its orders on from the block before.  A column draws only what it
depends on, so one worker can make one column.  No block repeats another:
a sum over half of the rows, doubled, is not the sum over all of them.
Every seed gives a block the same number of lineitems, so the blobs, and
the work, have the same sizes from seed to seed.  Quantities named in
``fixed`` are drawn alike for every seed: a configuration fixes those whose
encoded shapes follow the values drawn, so every seed runs the same
compiled programs.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

STARTDATE = 8035        # 1992-01-01
ENDDATE = 10591         # 1998-12-31
CURRENTDATE = 9298      # 1995-06-17

LINEITEM = ("L_ORDERKEY", "L_QUANTITY", "L_EXTENDEDPRICE", "L_DISCOUNT",
            "L_TAX", "L_RETURNFLAG", "L_LINESTATUS", "L_SHIPDATE",
            "L_COMMITDATE", "L_RECEIPTDATE", "L_SHIPMODE")
ORDERS = ("O_ORDERKEY",)
COLUMNS = LINEITEM + ORDERS
# one random stream per drawn quantity, so a column draws only its own
_STREAMS = ("orders", "ship", "commit", "receipt", "quantity", "partkey",
            "discount", "tax", "flag", "shipmode")


class _Block:
    """Lazily drawn quantities of one block."""

    def __init__(self, scale: float, seed: int, block: int, fixed=()):
        self.scale = scale
        self.seed = int(seed) & (2**64 - 1)
        self.block = int(block)
        self.fixed = frozenset(fixed)
        self.memo: dict[str, np.ndarray] = {}
        rng = self.rng("orders")
        n_orders = max(int(1_500_000 * scale), 64)
        # 1..7 lineitems an order, as in dbgen, from a fixed multiset in a
        # seeded order: every seed makes the same number of rows
        self.per_order = rng.permutation(np.resize(np.arange(1, 8), n_orders))
        i = np.arange(1, n_orders + 1, dtype=np.int64) + block * n_orders
        self.o_orderkey = ((i >> 3) << 5) | (i & 7)
        self.o_orderdate = rng.integers(STARTDATE, ENDDATE - 151 + 1,
                                        n_orders)
        self.n = int(self.per_order.sum())

    def rng(self, stream: str) -> np.random.Generator:
        seed = 0 if stream in self.fixed else self.seed
        return np.random.default_rng([seed, self.block,
                                      _STREAMS.index(stream)])

    def _draw(self, stream: str, lo: int, hi: int) -> np.ndarray:
        if stream not in self.memo:
            self.memo[stream] = self.rng(stream).integers(lo, hi, self.n,
                                                          dtype=np.int32)
        return self.memo[stream]

    def orderdate(self) -> np.ndarray:
        if "orderdate" not in self.memo:
            self.memo["orderdate"] = np.repeat(self.o_orderdate,
                                               self.per_order)
        return self.memo["orderdate"]

    def shipdate(self) -> np.ndarray:
        if "shipdate" not in self.memo:
            self.memo["shipdate"] = self.orderdate() + self._draw("ship", 1,
                                                                  122)
        return self.memo["shipdate"]

    def receiptdate(self) -> np.ndarray:
        return self.shipdate() + self._draw("receipt", 1, 31)

    def column(self, name: str) -> np.ndarray:
        if name == "O_ORDERKEY":
            return self.o_orderkey.astype(np.int32)
        if name == "L_ORDERKEY":
            return np.repeat(self.o_orderkey, self.per_order).astype(np.int32)
        if name == "L_SHIPDATE":
            return self.shipdate().astype(np.int32)
        if name == "L_COMMITDATE":
            return (self.orderdate()
                    + self._draw("commit", 30, 91)).astype(np.int32)
        if name == "L_RECEIPTDATE":
            return self.receiptdate().astype(np.int32)
        if name == "L_LINESTATUS":
            return (self.shipdate() > CURRENTDATE).astype(np.int32)
        if name == "L_RETURNFLAG":
            ra = np.where(self._draw("flag", 0, 2) == 0, np.uint8(ord("R")),
                          np.uint8(ord("A")))
            return np.where(self.receiptdate() > CURRENTDATE,
                            np.uint8(ord("N")), ra)
        if name == "L_QUANTITY":
            return self._draw("quantity", 1, 51).astype(np.int32)
        if name == "L_EXTENDEDPRICE":
            partkey = self._draw("partkey", 1,
                                 max(int(200_000 * self.scale), 1000) + 1)
            cents = 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1000)
            return (self._draw("quantity", 1, 51) * cents
                    / 100.0).astype(np.float32)
        if name == "L_DISCOUNT":
            return (self._draw("discount", 0, 11) / 100.0).astype(np.float32)
        if name == "L_TAX":
            return (self._draw("tax", 0, 9) / 100.0).astype(np.float32)
        if name == "L_SHIPMODE":
            return self._draw("shipmode", 0, 7).astype(np.int32)
        raise KeyError(f"the generator makes no column {name!r}")


def generate(names, block_scale: float, blocks: int, seed: int,
             fixed=()) -> dict[str, np.ndarray]:
    """The columns ``names`` of ``blocks`` blocks of ``block_scale`` each;
    the quantities in ``fixed`` (names of ``_STREAMS``) do not follow the
    seed."""
    unknown = sorted(set(names) - set(COLUMNS))
    if unknown:
        raise KeyError(f"the generator makes no column {unknown}")
    unknown = sorted(set(fixed) - set(_STREAMS))
    if unknown:
        raise KeyError(f"the generator draws no quantity {unknown}")

    def make(b: int):
        blk = _Block(block_scale, seed, b, fixed)
        return [blk.column(n) for n in names]

    # blocks are independent; NumPy releases the GIL in most of the work
    with ThreadPoolExecutor(min(blocks, os.cpu_count() or 1)) as pool:
        made = list(pool.map(make, range(blocks)))
    return {n: np.concatenate([cols[i] for cols in made])
            for i, n in enumerate(names)}
