"""Query-operator IR + QueryPlan-to-graph lowering (codec x operator fusion).

Late materialization: instead of decoding every column to HBM and then running
the engine over the materialized columns, a ``QueryPlan`` (compare/between
predicates, arithmetic projections, predicated sums, segment-sum group-by)
lowers onto the columns' decode stages as operator stages -- per-column
predicate masks plus one terminal ``Reduce`` -- and ``fusion.fuse`` grafts the
decode chains into them (rule 6).  The fused graph's output is a partial
aggregate (a few scalars or an 8-lane segment accumulator), so the decompressed
columns never round-trip through HBM.

Predicates are evaluated in compressed domain where the codec allows it:

  * bit-packed integers: compared pre-widening on the packed words
    (``algos.bitpack.compare_stage``);
  * dictionary columns with a bit-packed index: value bounds map to dictionary
    *code* bounds (``algos.dictionary.code_bounds``, ``np.unique`` sorts the
    dictionary) and the code range is compared pre-widening -- the dictionary
    gather never happens;
  * RLE columns: per-run, run-length-weighted (``algos.rle.run_reduce_graph``),
    never per-row;
  * everything else (e.g. float2int decimals): fused-post-decode -- the decode
    closure is composed into the operator stage, and the float comparison uses
    the exact arithmetic of the reference engine (bitwise-identical masks).

Columns whose decode is not Fully-Parallel (ANS, RLE inside a multi-column
query) fall back to **resident** inputs: decoded once by the normal executor
path and gathered at the global row index by every fused chunk launch
(``BufSpec("row")``).

Row-sharded over a mesh of chips, each chip sums its own rows' partials
into ``mesh_lanes`` accumulator lanes; ``compile_combine`` adds the chips'
accumulators on the device, so one D2H reads the answer.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fusion, ir as ir_mod, plan as plan_mod
from repro.core.patterns import (BufSpec, Ctx, FullyParallel, Reduce, Stage,
                                 arg_at)


# ------------------------------------------------------------- expression IR

@dataclasses.dataclass(frozen=True)
class Col:
    """Column reference; ``cast`` applies ``astype`` on read (e.g. uint8 flag
    bytes entering integer arithmetic)."""

    name: str
    cast: str = ""

    def eval(self, env: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
        v = env[self.name]
        return v.astype(jnp.dtype(self.cast)) if self.cast else v

    def cols(self) -> set[str]:
        return {self.name}

    def token(self) -> str:
        return f"col:{self.name}:{self.cast}"


@dataclasses.dataclass(frozen=True)
class Const:
    value: float

    def eval(self, env: Mapping[str, jnp.ndarray]):
        return self.value          # python scalar: weak-typed, like the engine

    def cols(self) -> set[str]:
        return set()

    def token(self) -> str:
        return f"const:{self.value!r}"


@dataclasses.dataclass(frozen=True)
class Bin:
    """Binary arithmetic node; op in '+', '-', '*', '%'."""

    op: str
    a: Any
    b: Any

    def eval(self, env: Mapping[str, jnp.ndarray]):
        x, y = self.a.eval(env), self.b.eval(env)
        if self.op == "+":
            return x + y
        if self.op == "-":
            return x - y
        if self.op == "*":
            return x * y
        if self.op == "%":
            return x % y
        raise ValueError(f"unknown op {self.op!r}")

    def cols(self) -> set[str]:
        return self.a.cols() | self.b.cols()

    def token(self) -> str:
        return f"({self.a.token()}{self.op}{self.b.token()})"


@dataclasses.dataclass(frozen=True)
class Pred:
    """Range predicate on one column; op in '<', '<=', '>=', '>', 'between'
    (inclusive both ends, like SQL BETWEEN)."""

    col: str
    op: str
    value: Any
    value2: Any = None

    def mask(self, v: jnp.ndarray) -> jnp.ndarray:
        if self.op == "<":
            return v < self.value
        if self.op == "<=":
            return v <= self.value
        if self.op == ">=":
            return v >= self.value
        if self.op == ">":
            return v > self.value
        if self.op == "between":
            return (v >= self.value) & (v <= self.value2)
        raise ValueError(f"unknown predicate op {self.op!r}")

    def int_range(self) -> tuple[int | None, int | None] | None:
        """As a half-open integer range [lo, hi), or None if not exact."""
        def ok(x):
            return x is not None and float(x) == int(x)
        if self.op == "<" and ok(self.value):
            return None, int(self.value)
        if self.op == "<=" and ok(self.value):
            return None, int(self.value) + 1
        if self.op == ">=" and ok(self.value):
            return int(self.value), None
        if self.op == ">" and ok(self.value):
            return int(self.value) + 1, None
        if self.op == "between" and ok(self.value) and ok(self.value2):
            return int(self.value), int(self.value2) + 1
        return None

    def token(self) -> str:
        return f"pred:{self.col}:{self.op}:{self.value!r}:{self.value2!r}"


@dataclasses.dataclass(frozen=True)
class QueryPlan:
    """A scan-filter-aggregate query: ANDed predicates, mask-weighted sum
    aggregates, optional segment-sum group-by.  A trailing selected-row count
    lane is always computed (selectivity feedback for the cost model);
    ``keep_count_lane`` includes it in the result (TPC-H Q1's count(*) lane)."""

    name: str
    predicates: tuple[Pred, ...] = ()
    aggregates: tuple[tuple[str, Any], ...] = ()    # (label, Expr)
    group_key: Any = None                           # Expr -> int32 segment ids
    n_segments: int = 1
    keep_count_lane: bool = False

    def columns(self) -> list[str]:
        seen: list[str] = []
        for p in self.predicates:
            if p.col not in seen:
                seen.append(p.col)
        for _, e in self.aggregates:
            for c in sorted(e.cols()):
                if c not in seen:
                    seen.append(c)
        if self.group_key is not None:
            for c in sorted(self.group_key.cols()):
                if c not in seen:
                    seen.append(c)
        return seen

    def digest(self) -> str:
        toks = [self.name, str(self.n_segments), str(self.keep_count_lane)]
        toks += [p.token() for p in self.predicates]
        toks += [f"{lbl}={e.token()}" for lbl, e in self.aggregates]
        if self.group_key is not None:
            toks.append(f"key={self.group_key.token()}")
        return hashlib.sha1("|".join(toks).encode()).hexdigest()[:16]


# ------------------------------------------------------------- accumulation

# Rows per partial sum.  A float32 accumulator's rounding error grows with the
# number of terms added into it: one pass over 6 M TPC-H rows already misses a
# 1e-4 relative bound against a float64 sum.  Partials over SUM_BLOCK rows, then
# one sum of the partials, keep both passes short on every backend.
SUM_BLOCK = 4096


def blocked_segment_sum(lanes: Sequence[jnp.ndarray],
                        key: jnp.ndarray | None = None,
                        num_segments: int = 1) -> jnp.ndarray:
    """Two-pass float32 sums of equal-length 1-D ``lanes``: grouped by
    ``key`` into ``num_segments`` rows -> (S, L), or over all rows -> (L,)
    when ``key`` is None.  Keys outside [0, S) drop, as in
    ``jax.ops.segment_sum``.  Groups are a one-hot select reduced over row
    blocks, not a scatter: on a TPU the compiler fuses the select into the
    reduction, where a scatter or a stacked (n, L) operand is materialized."""
    n = int(lanes[0].shape[0])
    block = max(1, min(SUM_BLOCK, n))
    pad = -n % block

    def blocks(v: jnp.ndarray) -> jnp.ndarray:
        return jnp.pad(v, (0, pad)).reshape(-1, block)

    if key is None:
        return jnp.stack([blocks(v).sum(1).sum(0) for v in lanes])
    onehot = blocks(key)[None] == jnp.arange(num_segments,
                                             dtype=key.dtype)[:, None, None]
    return jnp.stack([jnp.where(onehot, blocks(v)[None], 0).sum(2).sum(1)
                      for v in lanes], axis=1)


# A row-sharded query keeps one accumulator per chip, padded to a whole
# number of this many lanes, so every query's combine is one program.
MESH_LANES = 128


def mesh_lanes(n_out: int) -> int:
    """Accumulator lanes a chip holds for ``n_out`` partial aggregates."""
    return -(-int(n_out) // MESH_LANES) * MESH_LANES


def compile_combine(devices, lanes: int):
    """The combine of a row-sharded query: each chip's ``lanes`` partial
    aggregates (a global array sharded along the mesh axis) summed across
    the chips by one ``psum`` on the device; the (lanes,) result is
    replicated, so one D2H from any chip reads it."""
    from jax.sharding import PartitionSpec

    from repro.core.compiler import MESH_AXIS, _named, mesh_of

    def combine(acc):
        return jax.lax.psum(acc, MESH_AXIS)

    fn = jax.shard_map(_named(combine, "combine", "mesh"),
                       mesh=mesh_of(devices),
                       in_specs=PartitionSpec(MESH_AXIS),
                       out_specs=PartitionSpec())
    return jax.jit(fn)


# ------------------------------------------------------------------ lowering

def _all_fp(stages: list[Stage]) -> bool:
    return all(isinstance(st, FullyParallel) for st in stages)


def _merge_ranges(preds: tuple[Pred, ...]) -> tuple[int | None, int | None] | None:
    lo: int | None = None
    hi: int | None = None
    for p in preds:
        r = p.int_range()
        if r is None:
            return None
        plo, phi = r
        if plo is not None:
            lo = plo if lo is None else max(lo, plo)
        if phi is not None:
            hi = phi if hi is None else min(hi, phi)
    return lo, hi


def _compressed_domain_mask(col: str, enc, lo, hi) -> FullyParallel | None:
    """Pre-widening range mask over the packed words, or None if unsupported."""
    from repro.algos import bitpack as bp_mod
    from repro.algos import dictionary as dict_mod

    if enc.codec == "bitpack":
        return bp_mod.compare_stage(
            enc, f"{col}.packed", f"{col}.@bit_width", f"{col}.@base",
            f"{col}.mask", lo, hi)
    if enc.codec == "dictionary":
        child = enc.children.get("index")
        if child is None or child.codec != "bitpack":
            return None
        clo, chi = dict_mod.code_bounds(enc.buffers["dictionary"], lo, hi)
        return bp_mod.compare_stage(
            child, f"{col}/index.packed", f"{col}/index.@bit_width",
            f"{col}/index.@base", f"{col}.mask", clo, chi)
    return None


@dataclasses.dataclass
class FusedQuery:
    """A lowered, fused query: one Reduce-terminated DecodeGraph over the
    fusible columns plus the names of resident fallback columns."""

    qplan: QueryPlan
    graph: ir_mod.DecodeGraph
    operands: dict[str, np.ndarray]      # leaf buffers + meta operands (host)
    fused_cols: tuple[str, ...]
    resident: tuple[str, ...]            # columns fed decoded ("row" inputs)
    n_rows: int
    n_lanes: int                         # aggregates + the count lane
    n_segments: int
    prefuse_stages: list[Stage] = dataclasses.field(default_factory=list)

    def resident_input(self, col: str) -> str:
        return f"{col}.resident"

    def finalize(self, acc: jnp.ndarray) -> jnp.ndarray:
        """Partial-sum accumulator -> the engine-shaped result."""
        if self.qplan.group_key is None:
            vec = acc[: self.n_lanes - 1]
            return vec[0] if self.n_lanes == 2 else vec
        mat = acc.reshape(self.n_lanes, self.n_segments)
        return mat if self.qplan.keep_count_lane else mat[:-1]

    def selected_rows(self, acc: jnp.ndarray) -> float:
        return float(np.sum(np.asarray(acc[-self.n_segments:])))

    def selectivity(self, acc: jnp.ndarray) -> float:
        return self.selected_rows(acc) / max(self.n_rows, 1)


def lower_query(qplan: QueryPlan, encs: Mapping[str, Any]) -> FusedQuery:
    """Lower a QueryPlan over compressed columns to a fused DecodeGraph.

    ``encs`` maps column name -> ``plan.Encoded``; every column the query
    touches must be present and all columns must share the row count.
    """
    cols = qplan.columns()
    for c in cols:
        if c not in encs:
            raise KeyError(f"query {qplan.name} needs column {c!r}")
    n_rows = int(encs[cols[0]].n)
    for c in cols:
        if int(encs[c].n) != n_rows:
            raise ValueError(f"column {c} has {encs[c].n} rows, expected {n_rows}")

    value_cols: set[str] = set()
    for _, e in qplan.aggregates:
        value_cols |= e.cols()
    if qplan.group_key is not None:
        value_cols |= qplan.group_key.cols()

    stages: list[Stage] = []
    roles: list[tuple[str, str, str]] = []   # (kind, col, input name)
    inline_preds: list[Pred] = []
    fused_cols: list[str] = []
    resident: list[str] = []

    for col in cols:
        enc = encs[col]
        preds = tuple(p for p in qplan.predicates if p.col == col)
        dec_stages = plan_mod.lower(enc, prefix=col, out_name=f"{col}.val")
        if not _all_fp(dec_stages):
            resident.append(col)
            roles.append(("value", col, f"{col}.resident"))
            inline_preds += list(preds)
            continue
        fused_cols.append(col)
        if preds and col not in value_cols:
            rng = _merge_ranges(preds)
            cmask = (_compressed_domain_mask(col, enc, *rng)
                     if rng is not None else None)
            if cmask is not None:
                stages.append(cmask)         # decode chain elided entirely
                roles.append(("mask", col, cmask.out))
                continue
            # fused-post-decode mask stage (composed into the decode by rule 6)
            stages += dec_stages

            def mk_mask(ps):
                def fn(ctx: Ctx, v: jnp.ndarray) -> jnp.ndarray:
                    x = arg_at(ctx, 0, v)
                    m = ps[0].mask(x)
                    for p in ps[1:]:
                        m = m & p.mask(x)
                    return m
                return fn

            mst = FullyParallel(
                fn=mk_mask(preds), inputs=(f"{col}.val",),
                specs=(BufSpec("tile"),), out=f"{col}.mask", n_out=n_rows,
                out_dtype=jnp.bool_, elementwise=False, name=f"pred[{col}]")
            mst._positional_inputs = True   # type: ignore[attr-defined]
            stages.append(mst)
            roles.append(("mask", col, mst.out))
        else:
            stages += dec_stages
            roles.append(("value", col, f"{col}.val"))
            inline_preds += list(preds)

    n_lanes = len(qplan.aggregates) + 1     # + selected-row count lane
    S = int(qplan.n_segments)
    aggs = tuple(qplan.aggregates)
    key_expr = qplan.group_key
    role_list = list(roles)
    ipreds = tuple(inline_preds)

    def reduce_fn(ctx: Ctx, *blocks):
        env: dict[str, jnp.ndarray] = {}
        mask = None
        for j, (kind, cn, _) in enumerate(role_list):
            v = arg_at(ctx, j, blocks[j])
            if kind == "mask":
                mask = v if mask is None else mask & v
            else:
                env[cn] = v
        for p in ipreds:
            m = p.mask(env[p.col])
            mask = m if mask is None else mask & m
        if ctx.n_valid is not None:         # a chip's padded partition
            m = ctx.out_idx < ctx.n_valid
            mask = m if mask is None else mask & m
        w = (jnp.ones(ctx.out_idx.shape, jnp.float32) if mask is None
             else mask.astype(jnp.float32))
        lanes = [e.eval(env).astype(jnp.float32) * w for _, e in aggs] + [w]
        # two-pass blocked sums: one pass over a whole chunk in float32 misses
        # the float64 answer by more than 1e-4 at TPC-H SF 1 row counts
        if key_expr is None:
            return blocked_segment_sum(lanes)                  # (L,)
        key = key_expr.eval(env).astype(jnp.int32)
        seg = blocked_segment_sum(lanes, key, S)               # (S, L)
        return seg.T.reshape(-1)

    red = Reduce(
        fn=reduce_fn,
        inputs=tuple(inp for _, _, inp in roles),
        specs=tuple(BufSpec("row") if c in resident else BufSpec("tile")
                    for _, c, _ in roles),
        n_in=n_rows, out=f"{qplan.name}.agg", n_out=n_lanes * S,
        out_dtype=jnp.float32, name=f"reduce[{qplan.name}]")
    stages.append(red)

    prefuse = list(stages)
    fused = fusion.fuse(stages, final_out=red.out)

    # only ship what the fused program actually reads (a compressed-domain
    # predicate elides e.g. the dictionary buffer along with the decode)
    used: set[str] = set()
    for st in fused:
        used.update(getattr(st, "inputs", ()))
    operands: dict[str, np.ndarray] = {}
    buffers: list[ir_mod.BufferDef] = []
    meta_specs: list[ir_mod.MetaSpec] = []
    h = hashlib.sha1()
    for col in fused_cols:
        enc = encs[col]
        h.update(f"{col}:{ir_mod.structural_signature(enc)}".encode())
        for k, v in plan_mod.flat_buffers(enc, prefix=col).items():
            if k in used:
                operands[k] = v
                buffers.append(ir_mod.BufferDef(
                    name=k, shape=tuple(v.shape), dtype=np.dtype(v.dtype).str))
        for k, v in plan_mod.meta_operands(enc, prefix=col).items():
            if k in used:
                operands[k] = v
                meta_specs.append(ir_mod.MetaSpec(
                    name=k, shape=tuple(v.shape), dtype=np.dtype(v.dtype).str))
    for col in resident:
        h.update(f"row:{col}:{np.dtype(encs[col].dtype).str}".encode())
    h.update(qplan.digest().encode())

    graph = ir_mod.DecodeGraph(
        stages=fused, buffers=tuple(buffers), out=red.out,
        n_out=int(red.n_out), out_dtype="<f4",
        signature=h.hexdigest() + "+qfused", meta_specs=tuple(meta_specs),
        nesting=f"query[{qplan.name}]", fused=True)
    return FusedQuery(
        qplan=qplan, graph=graph, operands=operands,
        fused_cols=tuple(fused_cols), resident=tuple(resident),
        n_rows=n_rows, n_lanes=n_lanes, n_segments=S, prefuse_stages=prefuse)
