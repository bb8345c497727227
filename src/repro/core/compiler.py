"""ZipFlow compiler driver: DecodeGraph -> executable on-device program.

The compile pipeline is ``plan.lower_graph`` -> ``fusion.fuse_graph`` ->
``compile_graph``; compiled programs live in a ``ProgramCache`` keyed by the graph's
STRUCTURE-ONLY signature plus compile options, so N structurally identical columns
share ONE jitted executable (one trace, one XLA compile, one launch geometry) even
when their data-dependent meta differs: programs are *called* with an operand pytree
(leaf buffers + lifted meta scalars, ``plan.host_operands``), never specialized on
meta values.  ``compile_decoder`` remains as the thin per-blob compatibility shim
over that pipeline; ``get_chunk``/``compile_chunk_graph`` build the per-chunk decode
programs the streaming executor launches chunk-by-chunk.

Backends:
  * "jnp"      -- pure jax.numpy stages: the default, and the backend the streaming
                  executor runs on every platform.
  * "pallas"   -- the Pallas TPU kernels of ``repro.kernels``.  The caller passes
                  ``interpret`` (True off a TPU); nothing picks it from the backend.
                  The TPU compiler refuses these kernels today (1-D dynamic
                  indexing of VMEM-resident buffers), so they run in interpret mode
                  only.
  * "baseline" -- the nvCOMP role: fixed geometry, **no fusion**, every stage
                  materializes its output (paper §5.2/§5.3 baseline behaviour).
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import fusion as fusion_mod
from repro.core import plan as plan_mod
from repro.core.geometry import Geometry, chip as chip_spec, native_config, resolve_chip
from repro.core.ir import (DecodeGraph, element_chunk_layout, group_chunk_layout,
                           query_chunk_layout)
from repro.core.patterns import Aux, Ctx, GroupParallel, Stage


def _run_stage(st: Stage, bufs: dict[str, jnp.ndarray], backend: str,
               geoms: dict[str, Geometry], interpret: bool) -> jnp.ndarray:
    if backend == "pallas" and not isinstance(st, Aux):
        from repro.kernels import ops

        return ops.run_stage(st, bufs, geoms, interpret=interpret)
    return st.run_jnp(bufs)


BASELINE_GEOMS = {"fp": Geometry(1, 8, 128), "gp": Geometry(1, 8, 128),
                  "np": Geometry(1, 8, 128)}


@dataclasses.dataclass
class Program:
    """One compiled decode program, shared by every blob with the same signature.

    ``fn`` decodes a single column's operand dict (leaf buffers + lifted meta
    scalars); ``batched`` decodes a stack of same-signature columns in one launch
    (vmap over the leading axis -- meta operands stack and vmap with the buffers)
    -- built lazily because most programs only ever see one column.
    """

    fn: Callable[[dict[str, jnp.ndarray]], jnp.ndarray]
    raw_fn: Callable[[dict[str, jnp.ndarray]], jnp.ndarray]  # unjitted decode body
    graph: DecodeGraph
    backend: str
    jit: bool = True
    calls: int = 0              # single-column executions (0 => next call traces)
    batched_calls: int = 0      # batched executions
    _batched: Callable | None = dataclasses.field(default=None, repr=False)

    @property
    def signature(self) -> str:
        return self.graph.signature

    @property
    def stages(self) -> list[Stage]:
        return self.graph.stages

    @property
    def n_kernels(self) -> int:
        return len(self.graph.stages)

    def __call__(self, bufs: dict[str, jnp.ndarray]) -> jnp.ndarray:
        self.calls += 1
        return self.fn(bufs)

    def batched(self, stacked: dict[str, jnp.ndarray]) -> jnp.ndarray:
        """Decode K same-signature columns stacked on a new leading axis: one
        launch instead of K (multi-column batched decode)."""
        if self._batched is None:
            vfn = jax.vmap(self.raw_fn)
            self._batched = jax.jit(vfn) if self.jit else vfn
        self.batched_calls += 1
        return self._batched(stacked)


def compile_graph(graph: DecodeGraph, backend: str = "jnp",
                  chip: str | None = None,
                  geometry: dict[str, Geometry] | None = None,
                  interpret: bool | None = None,
                  jit: bool = True) -> Program:
    """Compile a DecodeGraph to a Program (no caching -- see ProgramCache)."""
    spec = chip_spec(chip)
    geoms = geometry or {p: native_config(p, spec) for p in ("fp", "gp", "np")}
    if backend == "baseline":
        # fixed library geometry, deliberately not adapted to the chip (paper §5.2)
        geoms = dict(BASELINE_GEOMS)
    if backend == "pallas" and interpret is None:
        raise ValueError("backend='pallas' needs interpret= passed explicitly "
                         "(True off a TPU)")
    stages = graph.stages

    def decode(bufs: dict[str, jnp.ndarray]) -> jnp.ndarray:
        env = dict(bufs)
        out = None
        for st in stages:
            out = _run_stage(st, env, backend, geoms, interpret)
            env[st.out] = out
        return out

    fn = jax.jit(decode) if jit else decode
    return Program(fn=fn, raw_fn=decode, graph=graph, backend=backend, jit=jit)


@dataclasses.dataclass
class ChunkProgram:
    """Per-chunk decode program: one launch decodes output elements
    [out_start, out_start + chunk_elems) from the chunk's buffer slices.

    ``fn(bufs, out_start)`` takes the chunk's tile-leaf slices plus the column's
    whole-resident buffers/meta operands, with ``out_start`` a traced scalar so the
    same program serves every chunk at its offset.  Executed with the stage
    closures' jnp semantics (the fns are backend-agnostic by construction)."""

    fn: Callable[[dict[str, jnp.ndarray], Any], jnp.ndarray]
    graph: DecodeGraph
    chunk_elems: int
    jit: bool = True
    calls: int = 0

    def __call__(self, bufs: dict[str, jnp.ndarray], out_start) -> jnp.ndarray:
        self.calls += 1
        return self.fn(bufs, out_start)


def compile_chunk_graph(graph: DecodeGraph, chunk_elems: int,
                        jit: bool = True) -> ChunkProgram:
    """Compile the per-chunk variant of an element-chunkable graph.

    Every stage is Fully-Parallel (``element_chunk_layout`` guarantees it), so the
    chunk evaluates each stage closure at the chunk's global output indices with
    tile inputs sliced to the chunk window: exactly the addressing the Pallas grid
    tiles use, at transfer-chunk granularity.  Tile origins for operand-driven
    ratios (bitpack's ``bit_width``) are computed from the traced operand, so one
    program serves columns with different widths too."""
    layout = element_chunk_layout(graph)
    if layout is None:
        raise ValueError(f"graph {graph.nesting!r} is not element-chunkable")
    stages = graph.stages

    def decode_chunk(bufs: dict[str, jnp.ndarray], out_start) -> jnp.ndarray:
        out_idx = out_start + jnp.arange(chunk_elems, dtype=jnp.int32)
        env = dict(bufs)
        produced: set[str] = set()
        out = None
        for st in stages:
            starts = []
            for nm, spec in zip(st.inputs, st.specs):
                if nm in produced or spec.kind == "full":
                    starts.append(None)     # positionally aligned / whole-resident
                elif spec.num_op:
                    num = env[spec.num_op][0]
                    starts.append((out_start * num) // spec.den)
                else:
                    starts.append((out_start * spec.num) // spec.den)
            ctx = Ctx(out_idx=out_idx, starts=tuple(starts))
            out = st.fn(ctx, *[env[nm] for nm in st.inputs]).astype(st.out_dtype)
            env[st.out] = out
            produced.add(st.out)
        return out

    fn = jax.jit(decode_chunk) if jit else decode_chunk
    return ChunkProgram(fn=fn, graph=graph, chunk_elems=int(chunk_elems), jit=jit)


@dataclasses.dataclass
class QueryChunkProgram:
    """Per-chunk fused-query program: one launch evaluates scan-filter-aggregate
    over item rows [out_start, out_start + chunk_elems) and returns a PARTIAL
    AGGREGATE vector (``graph.n_out`` accumulator lanes), not decoded rows.
    The executor sums partials across chunks on device; the decompressed
    columns never exist at HBM.  Body and tail chunks share programs per size
    like ``ChunkProgram``."""

    fn: Callable[[dict[str, jnp.ndarray], Any], jnp.ndarray]
    graph: DecodeGraph
    chunk_elems: int
    jit: bool = True
    calls: int = 0

    def __call__(self, bufs: dict[str, jnp.ndarray], out_start) -> jnp.ndarray:
        self.calls += 1
        return self.fn(bufs, out_start)


def compile_query_chunk_graph(graph: DecodeGraph, chunk_elems: int,
                              jit: bool = True) -> QueryChunkProgram:
    """Compile the per-chunk variant of a fused-query (Reduce-terminated) graph.

    Same addressing as ``compile_chunk_graph`` over the Reduce's ITEM axis,
    plus "row" inputs: decoded resident columns ride whole and are gathered at
    the chunk's global row indices (start 0)."""
    layout = query_chunk_layout(graph)
    if layout is None:
        raise ValueError(f"graph {graph.nesting!r} is not query-chunkable")
    stages = graph.stages
    # single-chunk program: the only start ever passed is 0, so bake it in as a
    # Python int -- every input offset folds to a constant and XLA's gather
    # simplifier turns ``block[iota - 0]`` into a plain read, where a traced
    # start forces real gathers through the whole fused body (measurably
    # slower on CPU)
    static0 = int(chunk_elems) >= int(stages[-1].n_in)

    def partial_chunk(bufs: dict[str, jnp.ndarray], out_start) -> jnp.ndarray:
        if static0:
            out_start = 0
        out_idx = out_start + jnp.arange(chunk_elems, dtype=jnp.int32)
        env = dict(bufs)
        produced: set[str] = set()
        out = None
        for st in stages:
            starts = []
            for nm, spec in zip(st.inputs, st.specs):
                if nm in produced or spec.kind == "full":
                    starts.append(None)     # positionally aligned / whole-resident
                elif spec.kind == "row":
                    starts.append(0)        # decoded resident: global gather
                elif static0:
                    starts.append(0)
                elif spec.num_op:
                    num = env[spec.num_op][0]
                    starts.append((out_start * num) // spec.den)
                else:
                    starts.append((out_start * spec.num) // spec.den)
            ctx = Ctx(out_idx=out_idx, starts=tuple(starts))
            out = st.fn(ctx, *[env[nm] for nm in st.inputs]).astype(st.out_dtype)
            env[st.out] = out
            produced.add(st.out)
        return out

    fn = jax.jit(partial_chunk) if jit else partial_chunk
    return QueryChunkProgram(fn=fn, graph=graph, chunk_elems=int(chunk_elems),
                             jit=jit)


# ------------------------------------------------------- group-boundary chunks

@dataclasses.dataclass
class PrologueProgram:
    """One-shot decode of everything upstream of a graph's group stage: presum
    auxes and nested child decodes, over whole-resident leaves.  Returns the
    resident intermediates the per-span launches gather from."""

    fn: Callable[[dict[str, jnp.ndarray]], dict[str, jnp.ndarray]]
    graph: DecodeGraph
    jit: bool = True
    calls: int = 0

    def __call__(self, bufs: dict[str, jnp.ndarray]) -> dict[str, jnp.ndarray]:
        self.calls += 1
        return self.fn(bufs)


def compile_group_prologue(graph: DecodeGraph, jit: bool = True
                           ) -> PrologueProgram | None:
    """Compile the prologue of a group-chunkable graph (None when the group
    stage is first and nothing precedes it, e.g. plain ANS)."""
    layout = group_chunk_layout(graph)
    if layout is None:
        raise ValueError(f"graph {graph.nesting!r} is not group-chunkable")
    if layout.stage_index == 0 or not layout.resident:
        return None
    pro = graph.stages[: layout.stage_index]
    needed = layout.resident

    def run_prologue(bufs: dict[str, jnp.ndarray]) -> dict[str, jnp.ndarray]:
        env = dict(bufs)
        for st in pro:
            env[st.out] = st.run_jnp(env)
        return {nm: env[nm] for nm in needed}

    fn = jax.jit(run_prologue) if jit else run_prologue
    return PrologueProgram(fn=fn, graph=graph, jit=jit)


@dataclasses.dataclass
class GroupChunkProgram:
    """Per-span decode program for group-boundary chunking: one launch decodes
    the ``g_size`` whole groups starting at group ``g_start``, producing
    ``pad_elems`` output elements of which the first ``n_valid`` are real
    (uneven group sizes pad body launches to a shared shape; the executor trims
    before concatenating).  ``out_start``/``g_start``/``n_valid`` are traced
    scalars, so ONE program serves every body span (and a second the tail)."""

    fn: Callable[..., jnp.ndarray]
    graph: DecodeGraph
    g_size: int
    pad_elems: int
    jit: bool = True
    calls: int = 0

    def __call__(self, bufs: dict[str, jnp.ndarray], out_start, g_start,
                 n_valid) -> jnp.ndarray:
        self.calls += 1
        return self.fn(bufs, out_start, g_start, n_valid)


def compile_group_chunk_graph(graph: DecodeGraph, g_size: int, pad_elems: int,
                              jit: bool = True) -> GroupChunkProgram:
    """Compile the per-span variant of a group-chunkable graph.

    The group stage re-evaluates its closures at the span's GLOBAL output
    indices: a Group-Parallel span searches the whole-resident presum (so group
    id and in-group position are exactly the whole-column values) and gathers
    sliced value leaves at span-local offsets; a Non-Parallel span lockstep-
    decodes its own column slice of the stripe.  Trailing Fully-Parallel stages
    use the element path's addressing.  Bitwise equality with whole-column
    decode holds by construction: same closures, same global indices, exact
    group-aligned slices."""
    layout = group_chunk_layout(graph)
    if layout is None:
        raise ValueError(f"graph {graph.nesting!r} is not group-chunkable")
    gst = graph.stages[layout.stage_index]
    post = graph.stages[layout.stage_index + 1:]
    g_size = int(g_size)
    pad_elems = int(pad_elems)

    def decode_span(bufs: dict[str, jnp.ndarray], out_start, g_start,
                    n_valid) -> jnp.ndarray:
        env = dict(bufs)
        j = jnp.arange(pad_elems, dtype=jnp.int32)
        # clamp padding lanes to the last valid element: always in-bounds, and
        # the executor trims [:n_valid] before concatenation
        out_idx = out_start + jnp.minimum(j, jnp.maximum(n_valid - 1, 0))
        if isinstance(gst, GroupParallel):
            presum = env[gst.presum]
            g = jnp.searchsorted(presum, out_idx, side="right").astype(
                jnp.int32) - 1
            pos = out_idx - presum[g]
            # span-time value grafts: re-evaluate the producer closure at the
            # span's global group indices over its sliced primary leaf -- the
            # block then reads exactly like a sliced value input starting at
            # g_start (bitwise the whole-column intermediate at those indices)
            for nm, gi in layout.span_graft.items():
                p = graph.stages[gi]
                gg = g_start + jnp.arange(g_size, dtype=jnp.int32)
                p_starts = []
                for i_nm, i_spec in zip(p.inputs, p.specs):
                    if i_spec.kind == "full":
                        p_starts.append(None)
                    elif i_spec.num_op:
                        p_starts.append(
                            (g_start * env[i_spec.num_op][0]) // i_spec.den)
                    else:
                        p_starts.append((g_start * i_spec.num) // i_spec.den)
                env[nm] = p.fn(Ctx(out_idx=gg, starts=tuple(p_starts)),
                               *[env[i] for i in p.inputs])
            starts = []
            for nm, spec in zip(gst.value_inputs, gst.value_specs):
                if nm in layout.span_graft:
                    starts.append(g_start)   # local block begins at the span
                elif nm not in layout.sliced:
                    starts.append(0)
                elif spec.num_op:
                    # operand-driven ratio (bitpack's bit_width): same floor
                    # formula the schedule builder slices with, traced so one
                    # program serves every span
                    starts.append((g_start * env[spec.num_op][0]) // spec.den)
                else:
                    starts.append((g_start * spec.num) // spec.den)
            starts = tuple(starts)
            ctx = Ctx(out_idx=out_idx, starts=starts)
            gval = gst.value_fn(ctx, g, *[env[nm] for nm in gst.value_inputs])
            extras = [env[nm] for nm in gst.extra_inputs]
            out = gst.map_fn(ctx, gval, pos, g, *extras).astype(gst.out_dtype)
        else:                                   # NonParallel span
            from repro.algos.ans import decode_chunks_jnp  # avoids import cycle

            syms = decode_chunks_jnp(
                env[gst.streams], env[gst.states], env[gst.sym_tab],
                env[gst.freq_tab], env[gst.cum_tab], gst.chunk_size)
            flat = syms.reshape(-1)             # g_size * chunk_size local bytes
            byte0 = g_start * gst.chunk_size
            if gst.out_map is not None:
                bctx = Ctx(out_idx=byte0 + jnp.arange(flat.shape[0],
                                                      dtype=jnp.int32),
                           starts=(None,))
                flat = gst.out_map(bctx, flat)
            out = flat.astype(gst.out_dtype)
            if not post:                        # final out must be pad-shaped
                out = out[jnp.minimum(j, jnp.maximum(n_valid - 1, 0))]
        env[gst.out] = out
        produced = {gst.out}
        for st in post:
            starts = []
            for nm, spec in zip(st.inputs, st.specs):
                if spec.kind == "full":
                    starts.append(None)
                elif nm in produced:
                    # local intermediate whose global origin is the span start
                    starts.append((out_start * spec.num) // spec.den)
                else:
                    starts.append(None)
            ctx = Ctx(out_idx=out_idx, starts=tuple(starts))
            out = st.fn(ctx, *[env[nm] for nm in st.inputs]).astype(st.out_dtype)
            env[st.out] = out
            produced.add(st.out)
        return out

    fn = jax.jit(decode_span) if jit else decode_span
    return GroupChunkProgram(fn=fn, graph=graph, g_size=g_size,
                             pad_elems=pad_elems, jit=jit)


def _geometry_key(geometry: dict[str, Geometry] | None):
    if geometry is None:
        return None
    return tuple(sorted(geometry.items()))


class ProgramCache:
    """Signature-keyed cache of compiled programs: one jit per *structure*.

    The key is (graph signature, backend, chip, geometry override, interpret, jit);
    everything value-dependent is already folded into the signature by the IR layer.
    ``max_programs`` bounds the cache LRU-style (None = unbounded): long-lived
    servers seeing unbounded shape variety (e.g. one signature per prompt length)
    should set it so old programs are evicted instead of retained forever.
    """

    def __init__(self, max_programs: int | None = None):
        self._programs: dict[tuple, Any] = {}   # insertion order = LRU order
        self._lock = threading.Lock()
        self._compiling: dict[tuple, threading.Lock] = {}   # per-key compile guard
        self.max_programs = max_programs
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._programs)

    @property
    def stats(self) -> dict[str, int]:
        # snapshot under the lock: concurrent submitters share one cache, and a
        # torn read (hits bumped, programs not yet) would miscount reuse
        with self._lock:
            return {"programs": len(self._programs), "hits": self.hits,
                    "misses": self.misses, "evictions": self.evictions}

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()
            self._compiling.clear()
            self.hits = self.misses = self.evictions = 0

    def _lookup(self, key: tuple):
        """Under self._lock: hit bookkeeping + LRU refresh."""
        prog = self._programs.get(key)
        if prog is not None:
            self.hits += 1
            if self.max_programs is not None:       # refresh LRU position
                self._programs[key] = self._programs.pop(key)
        return prog

    def _get(self, key: tuple, build: Callable[[], Any]):
        # Double-checked: fast-path lookup under self._lock, then a per-key
        # compile lock, then a RE-lookup under self._lock before building --
        # a racing thread that lost the key_lock race finds the winner's
        # program on the second check instead of compiling again.  The
        # DispatchEngine relies on this invariant (exactly one trace+compile
        # per signature no matter how many threads hit the cache), and its
        # transfer workers never call into here at all -- only dispatcher
        # threads trace.
        with self._lock:
            prog = self._lookup(key)
            if prog is not None:
                return prog
            key_lock = self._compiling.setdefault(key, threading.Lock())
        # serialize same-key compiles (different keys still compile concurrently)
        # so racing callers never duplicate a trace+XLA compile
        with key_lock:
            try:
                with self._lock:
                    prog = self._lookup(key)
                    if prog is not None:
                        return prog
                prog = build()
                with self._lock:
                    self._programs[key] = prog
                    self.misses += 1
                    while (self.max_programs is not None
                           and len(self._programs) > self.max_programs):
                        self._programs.pop(next(iter(self._programs)))
                        self.evictions += 1
            finally:
                with self._lock:
                    self._compiling.pop(key, None)
        return prog

    def get(self, graph: DecodeGraph, backend: str = "jnp",
            chip: str | None = None,
            geometry: dict[str, Geometry] | None = None,
            interpret: bool | None = None, jit: bool = True) -> Program:
        chip = resolve_chip(chip)
        key = (graph.signature, backend, chip, _geometry_key(geometry),
               interpret, jit)
        return self._get(key, lambda: compile_graph(
            graph, backend=backend, chip=chip, geometry=geometry,
            interpret=interpret, jit=jit))

    def get_chunk(self, graph: DecodeGraph, chunk_elems: int,
                  jit: bool = True) -> ChunkProgram:
        """Cached per-chunk program: one per (structure, chunk size), shared by
        every chunk at that size across all same-signature columns."""
        key = (graph.signature, "chunk", int(chunk_elems), jit)
        return self._get(key, lambda: compile_chunk_graph(
            graph, chunk_elems, jit=jit))

    def get_query_chunk(self, graph: DecodeGraph, chunk_elems: int,
                        jit: bool = True) -> QueryChunkProgram:
        """Cached fused-query chunk program: one per (structure, chunk size);
        body chunks share one program, the uneven tail gets a second."""
        key = (graph.signature, "qchunk", int(chunk_elems), jit)
        return self._get(key, lambda: compile_query_chunk_graph(
            graph, chunk_elems, jit=jit))

    def get_group_chunk(self, graph: DecodeGraph, g_size: int, pad_elems: int,
                        jit: bool = True) -> GroupChunkProgram:
        """Cached group-span program: one per (structure, groups-per-span,
        padded output shape) -- every body span of a column (and of every
        same-signature column with the same span geometry) shares one trace."""
        key = (graph.signature, "gchunk", int(g_size), int(pad_elems), jit)
        return self._get(key, lambda: compile_group_chunk_graph(
            graph, g_size, pad_elems, jit=jit))

    def get_group_prologue(self, graph: DecodeGraph,
                           jit: bool = True) -> PrologueProgram | None:
        """Cached prologue program for a group-chunkable graph; None when the
        group stage is first (nothing upstream to decode)."""
        layout = group_chunk_layout(graph)
        if layout is None:
            raise ValueError(f"graph {graph.nesting!r} is not group-chunkable")
        if layout.stage_index == 0 or not layout.resident:
            return None
        key = (graph.signature, "gprologue", jit)
        return self._get(key, lambda: compile_group_prologue(graph, jit=jit))


# Process-wide default cache: the ``compile_decoder`` shim and every executor that
# doesn't bring its own cache share it, so e.g. 100 same-plan columns anywhere in the
# process trace and XLA-compile exactly once.  Deliberately unbounded: analytics and
# benchmark workloads see a bounded set of structures.  A long-lived process decoding
# unbounded shape variety should bring its own ``ProgramCache(max_programs=...)``
# (ServeEngine's default executor does).
DEFAULT_CACHE = ProgramCache()


def build_graph(enc: plan_mod.Encoded, fuse: bool = True) -> DecodeGraph:
    """Lower + (optionally) fuse: the front half of the compile pipeline."""
    graph = plan_mod.lower_graph(enc)
    return fusion_mod.fuse_graph(graph) if fuse else graph


def compile_blob(enc: plan_mod.Encoded, backend: str = "jnp", fuse: bool = True,
                 chip: str | None = None,
                 geometry: dict[str, Geometry] | None = None,
                 interpret: bool | None = None, jit: bool = True,
                 cache: ProgramCache | None = None) -> Program:
    """Blob -> cached Program (the modern entry point)."""
    if backend == "baseline":
        fuse = False
    graph = build_graph(enc, fuse=fuse)
    cache = DEFAULT_CACHE if cache is None else cache
    return cache.get(graph, backend=backend, chip=chip, geometry=geometry,
                     interpret=interpret, jit=jit)


# --------------------------------------------------------------- compatibility shim

@dataclasses.dataclass
class CompiledDecoder:
    """Legacy per-blob handle; now a thin view over a cached Program."""

    fn: Callable[[dict[str, jnp.ndarray]], jnp.ndarray]
    stages: list[Stage]
    backend: str
    n_kernels: int
    program: Program | None = None

    def __call__(self, bufs: dict[str, jnp.ndarray]) -> jnp.ndarray:
        if self.program is not None:   # keep Program.calls (cold-detection) honest
            return self.program(bufs)
        return self.fn(bufs)


def compile_decoder(enc: plan_mod.Encoded, backend: str = "jnp", fuse: bool = True,
                    chip: str | None = None,
                    geometry: dict[str, Geometry] | None = None,
                    interpret: bool | None = None,
                    jit: bool = True) -> CompiledDecoder:
    prog = compile_blob(enc, backend=backend, fuse=fuse, chip=chip,
                        geometry=geometry, interpret=interpret, jit=jit)
    return CompiledDecoder(fn=prog.fn, stages=prog.stages, backend=backend,
                           n_kernels=prog.n_kernels, program=prog)


def device_buffers(enc: plan_mod.Encoded, device=None,
                   sharding=None) -> dict[str, jnp.ndarray]:
    """Move a blob's operands host->device: leaf buffers (the compressed transfer
    itself) plus the lifted meta operands the program consumes at call time."""
    ops = plan_mod.host_operands(enc)
    put = functools.partial(jax.device_put, device=sharding or device)
    return {k: put(v) for k, v in ops.items()}


def decode_on_device(enc: plan_mod.Encoded, backend: str = "jnp",
                     **kw: Any) -> jnp.ndarray:
    """One-shot helper: transfer + decode."""
    dec = compile_decoder(enc, backend=backend, **kw)
    return dec(device_buffers(enc))
