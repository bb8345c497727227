"""Streaming decode executor: plan-driven chunked transfer + per-chunk or
batched decode.

This is the runtime half of the compile pipeline (``plan.lower_graph`` ->
``fusion.fuse_graph`` -> ``ProgramCache``).  ``run`` *consumes* an
``ExecutionPlan`` (``core/planner.py``): issue order, per-column chunk size,
decode mode and in-flight window all come from the plan -- the executor contains
no scheduling heuristics of its own.  When no plan is passed, one is built from
the constructor defaults through the same planner (so the legacy knobs
``chunk_bytes`` / ``chunk_decode`` / ``prefetch_chunks`` survive only as inputs
to auto-planning).  Given a plan over a set of compressed blobs it

  1. splits every leaf buffer into the plan's per-column chunk sizes,
  2. issues transfers in plan order as async ``jax.device_put`` with the plan's
     bounded in-flight window (double buffering: chunks k+1..k+w are in flight
     while chunk k is consumed),
  3. decodes each column through its cached Program in the plan's decode mode:

     * **per-chunk** (element-chunkable graphs): every transferred chunk is
       decoded in its own launch while later chunks are still in flight --
       transfer/decode overlap *within* a column, the configuration the fig19
       ``Zc`` model describes.  Chunk slices are coordinated through the
       graph's ``ChunkLayout`` so outputs concatenate to exactly the one-shot
       result.  Group-chunkable graphs (RLE/DeltaStride expansions, ANS chunk
       grids -- ``ir.group_chunk_layout``) stream at group boundaries instead:
       a one-shot prologue decodes the whole-resident metadata (presums, nested
       children), then each transferred span of whole groups decodes in its own
       body/tail launch, outputs concatenated on device.  Graphs with neither
       layout (e.g. delta's cumsum) fall back to one whole-column launch.
     * **whole-column / batched-by-signature**: chunks reassemble on device and
       the column decodes in one launch; adjacent plan-marked "batched" columns
       sharing one Program stack into ONE launch (``Program.batched``, vmap over
       the leading axis -- lifted meta operands stack and vmap along), and

  4. feeds measured per-column (transfer_s, decode_s) actuals back into the
     ``CostModel`` so the next plan is built from calibrated predictions
     instead of re-measuring every column.

Chunked, batched and per-chunk execution are all bitwise-identical to the one-shot
path: chunks concatenate back to the exact source bytes, vmap runs the same program
per lane, and per-chunk launches evaluate the same stage closures at the same global
indices over exact slices.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import costmodel, planner as planner_mod
from repro.core import plan as plan_mod, scheduler
from repro.core.compiler import DEFAULT_CACHE, Program, ProgramCache
from repro.core.costmodel import CostModel, profile_from
from repro.core.geometry import resolve_chip
from repro.core.ir import DecodeGraph, element_chunk_layout, group_chunk_layout
from repro.core.planner import ExecutionPlan


# ragged ANS stripes: per-span row caps are rounded up to this many words so
# the number of distinct stripe shapes (= jit retraces of the span programs)
# stays bounded while still skipping most of the max_words padding
ROW_CAP_QUANTUM = 64


def split_chunks(arr: np.ndarray, chunk_bytes: int | None) -> list[np.ndarray]:
    """Split a host buffer into <=chunk_bytes pieces along axis 0 (2-D buffers like
    the ANS stream matrix chunk by rows).  Concatenating the pieces restores the
    buffer exactly, so chunked transfer cannot change decode results.  The piece
    count comes from ``costmodel.rows_per_chunk`` -- the same formula
    ``ColumnProfile.n_transfer_chunks`` predicts with, so plans match execution."""
    if (chunk_bytes is None or arr.ndim == 0 or arr.nbytes <= chunk_bytes
            or arr.shape[0] <= 1):
        return [arr]
    rows = costmodel.rows_per_chunk(arr.shape[0], arr.nbytes, chunk_bytes)
    return [arr[i:i + rows] for i in range(0, arr.shape[0], rows)]


@dataclasses.dataclass(frozen=True)
class ChunkSchedule:
    """Coordinated per-chunk slicing for one column (resolved from the graph's
    chunk layout and the column's actual operand values / group metadata).

    ``kind="element"`` is the Fully-Parallel path: every chunk covers a fixed
    element range and ``out_sizes == pad_sizes``.  ``kind="group"`` is the
    group-boundary path: chunk k decodes the ``g_sizes[k]`` whole groups from
    ``g_starts[k]`` in its own launch, producing ``pad_sizes[k]`` elements of
    which ``out_sizes[k]`` are valid (uneven group sizes pad body launches to
    one shared shape -- ONE body program plus one tail program per structure);
    ``axes`` gives per-leaf slice axes (the ANS stripe slices columns).
    """

    out_starts: tuple[int, ...]
    out_sizes: tuple[int, ...]
    slices: dict[str, list[tuple[int, int]]]   # tile leaf -> per-chunk [lo, hi)
    whole: tuple[str, ...]                     # transferred once, shared by chunks
    kind: str = "element"                      # "element" | "group"
    g_starts: tuple[int, ...] = ()             # group path: span start groups
    g_sizes: tuple[int, ...] = ()              # group path: groups per span
    pad_sizes: tuple[int, ...] = ()            # group path: padded launch elems
    axes: dict[str, int] = dataclasses.field(default_factory=dict)
    # unpadded ANS stripes: per-chunk row caps for axis-1 leaves -- span k of
    # the stripe transfers only streams[:row_caps[leaf][k], g0:g1] (the words
    # its groups actually consume, quantized) instead of all max_words rows
    row_caps: dict[str, tuple[int, ...]] = dataclasses.field(
        default_factory=dict)
    # host-sourced whole buffers (layout.host_push): staged alongside the
    # whole leaves but materialized from encoder metadata, not operands
    host_push: dict[str, np.ndarray] = dataclasses.field(default_factory=dict)

    @property
    def n_chunks(self) -> int:
        return len(self.out_starts)

    def piece(self, arr: np.ndarray, leaf: str, k: int) -> np.ndarray:
        """Host slice of ``leaf`` for chunk ``k`` (row-capped for ragged
        axis-1 stripes)."""
        lo, hi = self.slices[leaf][k]
        if self.axes.get(leaf, 0) == 0:
            return arr[lo:hi]
        caps = self.row_caps.get(leaf)
        rows = int(arr.shape[0]) if caps is None else caps[k]
        return np.ascontiguousarray(arr[:rows, lo:hi])


# --------------------------------------------------------- dispatch engine
#
# Transfer issuance and decode dispatch are split into two roles:
#
#   * an *issuer* owns the ordered transfer-item list of one host->device
#     link and commits ``jax.device_put`` for items the dispatcher has
#     allowed (the plan's in-flight window, expressed as an item watermark)
#     subject to the shared host-staging budget;
#   * the *decode driver* is a generator (``_decode_leg`` and the per-chunk
#     runners) that yields ``("need", n)`` before it touches staged items
#     < n, and launches span/chunk programs as soon as those commits land.
#
# ``_InlineIssuer`` reproduces the historical single-threaded behavior
# exactly (``advance`` == the old ``issue_until``); ``_WorkerIssuer`` moves
# the puts onto a per-link worker thread so H2D copies for chunks k+1..k+w
# genuinely overlap chunk k's decode launch.  Workers NEVER trace: they only
# call ``jax.device_put``; every ``ProgramCache.get_*`` (and therefore every
# jit trace/compile) happens on the dispatcher thread driving the generator.

# one transfer item: (column name for issue-time accounting, destination
# staging list, slot index, host piece)
_TransferItem = tuple  # (str, list, int, np.ndarray)


class _InlineIssuer:
    """Synchronous issuer: ``advance(target)`` commits items < target on the
    calling thread -- byte-for-byte the legacy ``issue_until`` closure."""

    def __init__(self, items: list, device, issue_s: dict[str, float]):
        self._items = items
        self._device = device
        self._scan = obs.current_scan()
        self.issue_s = issue_s
        self.total = len(items)
        self.committed = 0

    def advance(self, target: int) -> None:
        while self.committed < min(target, self.total):
            name, dest, i, piece = self._items[self.committed]
            with obs.span("zipflow.put", column=name, bytes=piece.nbytes,
                          scan=self._scan) as put:
                dest[i] = jax.device_put(piece, self._device)   # async H2D
            self.issue_s[name] = self.issue_s.get(name, 0.0) + put.s
            self.committed += 1

    def wait(self, target: int) -> None:      # advance already committed them
        pass

    def consumed(self, upto: int) -> None:    # no staging budget to release
        pass

    def close(self) -> None:
        pass


class _WorkerIssuer:
    """One transfer-worker thread for one host->device link.

    The dispatcher advances an item watermark (``advance``, the plan's
    in-flight window); the worker commits ``device_put`` for allowed items
    strictly in list order, acquiring one shared host-staging slot per
    chunk-holding chunk (``acq``/``rel`` flags mark the first/last item of
    each per-chunk-decode chunk, mirroring ``simulate_stream_multi``'s
    budget unit).  The dispatcher releases slots as it consumes decoded
    chunks (``consumed``).  Worker exceptions surface on the dispatcher's
    next ``wait``/``check_error``; the worker never traces (puts only).
    """

    def __init__(self, items: list, device, issue_s: dict[str, float],
                 acq: Sequence[bool] | None = None,
                 rel: Sequence[bool] | None = None,
                 budget: threading.BoundedSemaphore | None = None,
                 cv: threading.Condition | None = None,
                 name: str = "zipflow-xfer", sync: bool = False):
        self._items = items
        self._device = device
        self._sync = sync
        # the dispatcher's scan: puts on the worker thread carry its id
        self._scan = obs.current_scan()
        self.issue_s = issue_s
        self.total = len(items)
        self.committed = 0
        self._allowed = 0
        self._acq = acq
        self._rel = rel
        self._budget = budget
        self._rel_ptr = 0
        self._stop = False
        self.error: BaseException | None = None
        self._cv = cv if cv is not None else threading.Condition()
        self._thread = threading.Thread(target=self._work, name=name,
                                        daemon=True)
        self._thread.start()

    # ----- worker side
    def _work(self) -> None:
        try:
            i = 0
            while i < self.total:
                with self._cv:
                    while self._allowed <= i and not self._stop:
                        self._cv.wait()
                    if self._stop:
                        return
                    hi = min(self._allowed, self.total)
                while i < hi:
                    name, dest, slot, piece = self._items[i]
                    if self._budget is not None and self._acq is not None \
                            and self._acq[i]:
                        # shared pinned-host-staging budget: one slot per
                        # transferred-but-undecoded chunk across ALL links
                        while not self._budget.acquire(timeout=0.1):
                            if self._stop:
                                return
                    with obs.span("zipflow.put", column=name,
                                  bytes=piece.nbytes, scan=self._scan) as put:
                        buf = jax.device_put(piece, self._device)  # async H2D
                        if self._sync:
                            # D2D copy legs block here so issue_s records
                            # the true copy duration (this worker has nothing
                            # else to do; the dispatcher keeps launching
                            # decodes)
                            jax.block_until_ready(buf)
                    self.issue_s[name] = self.issue_s.get(name, 0.0) + put.s
                    dest[slot] = buf
                    with self._cv:
                        self.committed = i + 1
                        self._cv.notify_all()
                    i += 1
        except BaseException as e:          # surfaced at the next wait()
            with self._cv:
                self.error = e
                self._cv.notify_all()

    # ----- dispatcher side
    def advance(self, target: int) -> None:
        target = min(target, self.total)
        with self._cv:
            if target > self._allowed:
                self._allowed = target
                self._cv.notify_all()

    def wait(self, target: int) -> None:
        """Block until items < target are committed (or raise the worker's
        exception)."""
        target = min(target, self.total)
        with self._cv:
            while self.committed < target:
                if self.error is not None:
                    raise RuntimeError(
                        "transfer worker failed") from self.error
                self._cv.wait(timeout=0.5)

    def check_error(self) -> None:
        if self.error is not None:
            raise RuntimeError("transfer worker failed") from self.error

    def consumed(self, upto: int) -> None:
        """Dispatcher consumed items < upto: release their chunks' staging
        slots (called from the one dispatcher thread only)."""
        if self._budget is None or self._rel is None:
            return
        upto = min(upto, self.total)
        while self._rel_ptr < upto:
            if self._rel[self._rel_ptr]:
                self._budget.release()
            self._rel_ptr += 1

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout=30.0)


class DispatchEngine:
    """Async dispatch engine: per-link transfer workers + ONE decode
    dispatcher.

    ``issuer`` spawns a ``_WorkerIssuer`` bound to this engine's shared
    condition (so any link's commit wakes the dispatcher) and shared
    host-staging budget (``LinkTopology.host_window``).  ``drive`` round-
    robins a set of decode-driver generators -- one per device leg -- on the
    calling thread: a leg is resumed as soon as its pending ``("need", n)``
    is satisfied, so decode launches for device A interleave with device B's
    while every link's worker keeps its H2D stream busy.  All tracing /
    compilation happens here, on the dispatcher thread; workers only
    ``device_put``.  Liveness: a leg's needs are satisfied in item order and
    staging slots are released as chunks are consumed, so every held slot
    belongs to a chunk some leg will consume without further budget -- the
    any-progress loop cannot deadlock.
    """

    def __init__(self, host_window: int | None = None):
        self._cv = threading.Condition()
        self._budget = (None if host_window is None
                        else threading.BoundedSemaphore(max(1, host_window)))
        self._issuers: list[_WorkerIssuer] = []

    def issuer(self, items: list, device, issue_s: dict[str, float],
               acq: Sequence[bool] | None = None,
               rel: Sequence[bool] | None = None,
               name: str = "zipflow-xfer",
               sync: bool = False) -> _WorkerIssuer:
        iss = _WorkerIssuer(items, device, issue_s, acq=acq, rel=rel,
                            budget=self._budget, cv=self._cv, name=name,
                            sync=sync)
        self._issuers.append(iss)
        return iss

    def drive(self, tasks: dict) -> dict:
        """``tasks``: key -> (generator, issuer).  Returns key -> generator
        return value.  Must be called from the thread that owns tracing."""
        results: dict = {}
        live = dict(tasks)
        need: dict = {k: None for k in tasks}      # None = not yet started
        while live:
            progressed = False
            for key in list(live):
                gen, iss = live[key]
                n = need[key]
                if n is not None and iss.committed < min(n, iss.total):
                    iss.check_error()
                    continue
                try:
                    # engine mode reports no per-wait residual: the wait
                    # happened while OTHER legs were being dispatched
                    _, need[key] = gen.send(None if n is None else 0.0)
                except StopIteration as stop:
                    results[key] = stop.value
                    del live[key]
                progressed = True
            if live and not progressed:
                with self._cv:
                    any_err = any(i.error is not None for _, i in live.values())
                    if not any_err and all(
                            i.committed < min(need[k], i.total)
                            for k, (_, i) in live.items()):
                        # every leg waits on a worker's device_put: the
                        # issue step, not a transfer's completion
                        with obs.span("zipflow.wait_issue"):
                            self._cv.wait(timeout=0.05)
        return results

    def close(self) -> None:
        for iss in self._issuers:
            iss.close()


def _drive_seq(gen, issuer):
    """Drive ONE decode-leg generator to completion on the calling thread,
    timing each transfer wait and feeding it back as the generator's residual.
    With an ``_InlineIssuer`` (whose ``wait`` is a no-op because ``advance``
    already committed synchronously) this reproduces the legacy sequential
    executor exactly."""
    wait_s = None
    while True:
        try:
            _, n = gen.send(wait_s)
        except StopIteration as stop:
            return stop.value
        with obs.span("zipflow.wait_h2d") as wait:
            issuer.wait(n)
        wait_s = wait.s


@dataclasses.dataclass
class _StagedLeg:
    """Host-staged transfer state for one device leg (one ``run`` call or
    the whole-column part of one mesh device): the ordered decode units plus
    the GLOBAL transfer-item indices each unit needs committed."""

    decisions: dict
    scheds: dict[str, ChunkSchedule | None]
    staged: dict[str, dict[str, list]]
    col_end: dict[str, int]
    chunk_ends: dict[str, list[int]]
    units: list
    window: int


@dataclasses.dataclass
class ColumnExec:
    """Execution record for one decoded column."""

    name: str
    array: jnp.ndarray
    transfer_s: float
    decode_s: float
    compressed_bytes: int
    plain_bytes: int
    n_chunks: int
    signature: str
    batched_with: tuple[str, ...] = ()   # same-signature columns sharing the launch
    decode_launches: int = 1             # >1 iff the per-chunk path ran
    chunk_decoded: bool = False
    shard_devices: tuple[int, ...] = ()  # mesh path: device id per group shard


@dataclasses.dataclass
class QueryExec:
    """Execution record for one decode-fused query (late materialization).

    ``traffic_bytes`` is the fused graph's modeled HBM traffic (leaf reads +
    the ``n_out`` accumulator lanes); ``prefuse_traffic_bytes`` prices the same
    stage list before operator fusion, where every decoded column and mask
    round-trips HBM -- the delta is what fusion removed."""

    name: str
    result: jnp.ndarray
    acc: jnp.ndarray                  # raw partial-aggregate lanes
    transfer_s: float
    decode_s: float
    n_chunks: int
    decode_launches: int
    selectivity: float
    compressed_bytes: int
    plain_bytes: int                  # decoded bytes that were NEVER written
    traffic_bytes: int
    prefuse_traffic_bytes: int
    resident: dict[str, ColumnExec] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class MeshRunResult:
    """Execution record for one ``run_sharded`` over a device mesh.

    ``columns`` maps every requested column to its record (sharded columns
    appear once, assembled); ``per_device`` lists the plan items each logical
    device executed and ``device_launches`` its decode-launch count.
    ``d2d_copies`` records each executed redistribution leg as
    ``item -> (src physical device, dst physical device, measured copy
    seconds)`` -- empty when the plan carried no redistribution."""

    columns: dict[str, ColumnExec]
    per_device: dict[int, tuple[str, ...]]
    device_launches: dict[int, int]
    plan: "planner_mod.MeshExecutionPlan"
    d2d_copies: dict[str, tuple[int, int, float]] = dataclasses.field(
        default_factory=dict)

    def __getitem__(self, name: str) -> ColumnExec:
        return self.columns[name]


class StreamingExecutor:
    """Plan-driven chunked, cached, batched/per-chunk decode engine.

    ``chunk_bytes`` (an int, None for whole-blob, or "auto" for per-column
    sizing), ``chunk_decode`` and ``prefetch_chunks`` are *planner defaults*:
    they parameterize the ``ExecutionPlan`` built when ``run`` is called
    without one; a passed plan is authoritative.
    """

    def __init__(self, backend: str = "jnp", fuse: bool = True,
                 chunk_bytes: int | None | str = 1 << 20, pipeline: bool = True,
                 batch_columns: bool = True, prefetch_chunks: int | None = None,
                 chunk_decode: bool = False,
                 chip: str | None = None, cache: ProgramCache | None = None,
                 policy: str = "chunk-johnson",
                 cost_model: CostModel | None = None,
                 async_dispatch: bool = False):
        self.backend = backend
        self.fuse = fuse
        self.chunk_bytes = chunk_bytes
        self.pipeline = pipeline
        # True routes single-device runs through the DispatchEngine (transfer
        # worker thread + decode dispatcher) by default; run(async_dispatch=..)
        # overrides per call.  Mesh runs overlap devices regardless (see
        # run_sharded(concurrent=...)).
        self.async_dispatch = async_dispatch
        self.batch_columns = batch_columns
        self.prefetch_chunks = (None if prefetch_chunks is None
                                else max(1, prefetch_chunks))
        self.chunk_decode = chunk_decode
        self.chip = resolve_chip(chip)
        self.cache = cache if cache is not None else DEFAULT_CACHE
        self.policy = policy
        self.cost_model = cost_model or CostModel(chip=self.chip)
        self._encoded: dict[str, plan_mod.Encoded] = {}
        self._graphs: dict[str, DecodeGraph] = {}
        self._programs: dict[str, Program] = {}
        self._chunk_counts: dict[tuple[str, int | None], int] = {}
        self._schedules: dict[tuple[str, int | None], ChunkSchedule | None] = {}
        # fused-query row-axis schedules + traffic accounting, keyed by the
        # fused graph's signature (which folds in the query digest and every
        # column's structure) -- warm run_query calls skip re-deriving both
        self._query_schedules: dict[tuple, tuple] = {}
        self._query_traffic: dict[str, tuple[int, int]] = {}
        # column set -> (order, decisions) of its latest plan, to count
        # plan changes (obs counter ``plan_changes``)
        self._last_plans: dict[frozenset, tuple] = {}
        # measured (transfer_s, decode_s) per column from the latest run --
        # an ALIAS of the cost model's store (one source of truth)
        self.timings: dict[str, tuple[float, float]] = self.cost_model.measured

    @property
    def _fixed_chunk_bytes(self) -> int | None:
        """Constructor chunk size as an int/None ("auto" falls back to the
        default fixed size for legacy single-size paths)."""
        cb = self.chunk_bytes
        return planner_mod.DEFAULT_CHUNK_BYTES if isinstance(cb, str) else cb

    # ------------------------------------------------------------------ compile
    def compile(self, name: str, enc: plan_mod.Encoded) -> Program:
        """Register a blob and return its (cache-shared) Program."""
        from repro.core.compiler import compile_blob

        self._encoded[name] = enc
        # re-registering a name invalidates anything derived from the old blob
        for store in (self._chunk_counts, self._schedules):
            for key in [k for k in store if k[0] == name]:
                store.pop(key)
        self.cost_model.forget(name)    # drops profile + measured timings
        prog = compile_blob(enc, backend=self.backend, fuse=self.fuse,
                            chip=self.chip, cache=self.cache)
        self._graphs[name] = prog.graph
        self._programs[name] = prog
        self.cost_model.register(profile_from(name, enc, prog.graph))
        return prog

    def column_profile(self, name: str):
        """Planner-facing profile of a registered column."""
        if name not in self.cost_model.profiles:
            self.cost_model.register(
                profile_from(name, self._encoded[name], self._graphs[name]))
        return self.cost_model.profiles[name]

    def program(self, name: str) -> Program:
        return self._programs[name]

    def graph(self, name: str) -> DecodeGraph:
        return self._graphs[name]

    # ----------------------------------------------------------------- schedule
    _DEFAULTS = object()     # sentinel: "use the constructor's chunk config"

    def _n_chunks(self, name: str, chunk_bytes: int | None | object = _DEFAULTS
                  ) -> int:
        """Number of transfer pieces the executor will issue for a column's leaf
        buffers (row-granular) -- the chunk count the Zc model uses.  Lifted meta
        operands ride along as extra scalar puts but are not counted."""
        if chunk_bytes is self._DEFAULTS:
            chunk_bytes = self._fixed_chunk_bytes
        if chunk_bytes is None:
            return 1
        cached = self._chunk_counts.get((name, chunk_bytes))
        if cached is None:
            flat = plan_mod.flat_buffers(self._encoded[name])
            cached = sum(len(split_chunks(np.asarray(v), chunk_bytes))
                         for v in flat.values())
            self._chunk_counts[(name, chunk_bytes)] = cached
        return cached

    def chunk_schedule(self, name: str,
                       chunk_bytes: int | None | object = _DEFAULTS
                       ) -> ChunkSchedule | None:
        """Coordinated per-chunk decode schedule for a column at the given chunk
        size, or None when the graph is not element-chunkable / chunking is off /
        one chunk suffices.  Without an explicit size, the constructor defaults
        gate it (chunk_decode flag + fixed chunk size), preserving the legacy
        probe semantics."""
        if chunk_bytes is self._DEFAULTS:
            if not self.chunk_decode:
                return None
            chunk_bytes = self._fixed_chunk_bytes
        if chunk_bytes is None:
            return None
        key = (name, chunk_bytes)
        if key in self._schedules:
            return self._schedules[key]
        sched = self._build_schedule(name, chunk_bytes)
        self._schedules[key] = sched
        return sched

    def _build_schedule(self, name: str,
                        chunk_bytes: int) -> ChunkSchedule | None:
        graph = self._graphs[name]
        layout = element_chunk_layout(graph)
        if layout is None:
            return self._build_group_schedule(name, chunk_bytes)
        ops = plan_mod.host_operands(self._encoded[name])
        # resolve tile ratios (operand-driven ratios use this column's meta value)
        ratios: dict[str, tuple[int, int]] = {}
        per_elem = 0.0
        for nm, spec in layout.tiled.items():
            num = int(ops[spec.num_op][0]) if spec.num_op else int(spec.num)
            ratios[nm] = (num, int(spec.den))
            per_elem += num / spec.den * np.dtype(ops[nm].dtype).itemsize
        n = int(graph.n_out)
        align = int(layout.align)
        # chunk size targets ~chunk_bytes of *compressed* tile bytes per chunk,
        # rounded to the alignment every boundary must respect -- via the same
        # shared formula ColumnProfile.decode_chunking predicts with
        chunk_elems = costmodel.aligned_chunk_elems(chunk_bytes, per_elem, align)
        if chunk_elems >= n:
            return None                      # degenerate: one chunk = whole column
        out_starts = tuple(range(0, n, chunk_elems))
        out_sizes = tuple(min(chunk_elems, n - s) for s in out_starts)
        slices: dict[str, list[tuple[int, int]]] = {}
        for nm, (num, den) in ratios.items():
            length = int(ops[nm].shape[0])
            per = []
            for s, sz in zip(out_starts, out_sizes):
                lo = (s * num) // den
                # the final chunk takes the remaining rows (incl. guard words);
                # interior boundaries are aligned so (b*num) % den == 0 exactly
                hi = length if s + sz >= n else ((s + sz) * num) // den
                per.append((lo, max(hi, lo + 1)))
            slices[nm] = per
        return ChunkSchedule(out_starts=out_starts, out_sizes=out_sizes,
                             slices=slices, whole=layout.whole)

    def _build_group_schedule(self, name: str, chunk_bytes: int,
                              g_lo: int = 0, g_hi: int | None = None,
                              force: bool = False) -> ChunkSchedule | None:
        """Group-boundary schedule: spans of whole groups sized to ~chunk_bytes
        of streamed group bytes, boundaries snapped to the encoder-emitted
        group-boundary prefix sums -- via the same shared formulas
        (``costmodel.groups_per_chunk`` / ``group_bytes_per_group``) the
        planner's ``ColumnProfile`` predicts with, so planned span counts equal
        executed span counts.

        ``g_lo``/``g_hi`` restrict the schedule to a group range (a mesh
        shard); ``g_starts``/``out_starts`` stay GLOBAL so the cached span
        programs decode shard-local at the right output offsets.  ``force``
        returns a schedule even when one span would cover the range (shards
        always need one, the whole-column path treats that as "don't chunk")."""
        graph = self._graphs[name]
        layout = group_chunk_layout(graph)
        if layout is None:
            return None
        ops = plan_mod.host_operands(self._encoded[name])
        n_groups = int(layout.n_groups)
        g_hi = n_groups if g_hi is None else min(int(g_hi), n_groups)
        g_lo = max(0, int(g_lo))
        span_groups = g_hi - g_lo
        bpg = costmodel.group_bytes_per_group(layout, ops)
        if span_groups < 1 or bpg <= 0 and not force:
            return None
        if n_groups <= 1 and not force:
            return None
        G = costmodel.groups_per_chunk(chunk_bytes, max(bpg, 1e-9),
                                       layout.align_groups)
        if G >= span_groups and not force:
            return None                  # degenerate: one span = whole column
        G = max(1, min(G, span_groups))
        presum = np.asarray(layout.group_presum, dtype=np.int64)
        g_starts = tuple(range(g_lo, g_hi, G))
        g_sizes = tuple(min(G, g_hi - s) for s in g_starts)
        out_starts = tuple(int(presum[s]) for s in g_starts)
        out_sizes = tuple(int(presum[s + z] - presum[s])
                          for s, z in zip(g_starts, g_sizes))
        if min(out_sizes) <= 0:
            return None                  # empty span (defensive; groups are >=1)
        if layout.elems_per_group:
            # uniform groups (ANS chunk grid): launches produce exactly the
            # decoded span, no padding needed
            pad_sizes = tuple(z * layout.elems_per_group for z in g_sizes)
        else:
            body = [sz for sz, z in zip(out_sizes, g_sizes) if z == G]
            body_pad = costmodel.pad_group_elems(max(body)) if body else 0
            pad_sizes = tuple(
                body_pad if z == G else costmodel.pad_group_elems(sz)
                for sz, z in zip(out_sizes, g_sizes))
        slices: dict[str, list[tuple[int, int]]] = {}
        for nm, spec in layout.sliced.items():
            arr = ops[nm]
            axis = layout.axes.get(nm, 0)
            length = int(arr.shape[axis])
            num = int(ops[spec.num_op][0]) if spec.num_op else int(spec.num)
            per = []
            for s, z in zip(g_starts, g_sizes):
                if axis == 1:
                    per.append((s, s + z))          # stripe: exact columns
                    continue
                lo = (s * num) // spec.den
                # the final span takes the remaining rows (incl. guard words);
                # interior boundaries are group-aligned so slices are integral
                if s + z >= n_groups:
                    hi = length
                elif spec.num_op:
                    # dynamic ratios (bitpack words) floor at span starts:
                    # round the end up and keep the cross-word guard the
                    # decode closure's straddle read touches
                    hi = min(length, -(-((s + z) * num) // spec.den) + 1)
                else:
                    hi = ((s + z) * num) // spec.den
                per.append((lo, max(hi, lo + 1)))
            slices[nm] = per
        # unpadded ANS stripes: when the encoder emitted per-chunk word counts,
        # each span only transfers the stripe rows its own groups consume
        # (quantized to ROW_CAP_QUANTUM so span-program retraces stay bounded)
        row_caps: dict[str, tuple[int, ...]] = {}
        gw = self._host_group_words(graph, layout)
        if gw is not None and len(gw) >= g_hi:
            for nm, axis in layout.axes.items():
                if axis != 1 or nm not in layout.sliced:
                    continue
                max_rows = int(np.asarray(ops[nm]).shape[0])
                caps = []
                for s, z in zip(g_starts, g_sizes):
                    need = max(1, int(np.max(gw[s:s + z])))
                    q = -(-need // ROW_CAP_QUANTUM) * ROW_CAP_QUANTUM
                    caps.append(min(max_rows, q))
                row_caps[nm] = tuple(caps)
        return ChunkSchedule(
            out_starts=out_starts, out_sizes=out_sizes, slices=slices,
            whole=layout.whole, kind="group", g_starts=g_starts,
            g_sizes=g_sizes, pad_sizes=pad_sizes, axes=dict(layout.axes),
            row_caps=row_caps,
            host_push=dict(getattr(layout, "host_push", None) or {}))

    @staticmethod
    def _host_group_words(graph: DecodeGraph, layout) -> np.ndarray | None:
        """Encoder-emitted per-chunk compressed word counts for the layout's
        ANS stripe, or None when the stage doesn't carry them."""
        if getattr(layout, "kind", None) != "np":
            return None
        stage = graph.stages[layout.stage_index]
        gw = getattr(stage, "host_group_words", None)
        return None if gw is None else np.asarray(gw)

    def shard_schedule(self, name: str, chunk_bytes: int | None,
                       g_lo: int, g_hi: int) -> ChunkSchedule | None:
        """Group-span schedule restricted to ``[g_lo, g_hi)`` (mesh shards).
        Always returns a schedule for group-chunkable columns (``force=True``:
        a shard needs one even when it fits a single span)."""
        key = (name, chunk_bytes, (int(g_lo), int(g_hi)))
        if key in self._schedules:
            return self._schedules[key]
        cb = (planner_mod.DEFAULT_CHUNK_BYTES if chunk_bytes is None
              else chunk_bytes)
        sched = self._build_group_schedule(name, cb, g_lo=g_lo, g_hi=g_hi,
                                           force=True)
        self._schedules[key] = sched
        return sched

    def issue_order(self, names: Sequence[str] | None = None) -> list[str]:
        """Column issue order from the configured scheduling policy."""
        names = list(self._encoded) if names is None else list(names)
        if not self.pipeline or len(names) <= 1:
            return names
        return list(self.plan(names).order)

    def plan(self, names: Sequence[str] | None = None,
             policy: str | None = None, order: Sequence[str] | None = None,
             chunk_bytes: int | None | str | object = _DEFAULTS,
             chunk_decode: bool | None = None,
             window: int | None = None,
             fused_columns=None) -> ExecutionPlan:
        """Build an ``ExecutionPlan`` for a set of registered columns.

        Defaults come from the constructor knobs; any argument overrides them.
        An explicit ``order`` pins the issue order (decisions still planned);
        ``pipeline=False`` degrades to submission order (FIFO).
        ``fused_columns`` maps columns a pending query could decode-fuse to a
        selectivity estimate (None = learned EWMA) -- see
        ``planner.plan_execution``.
        """
        names = list(self._encoded) if names is None else list(names)
        # an explicit policy always wins; pipeline=False only downgrades the
        # constructor DEFAULT to submission order
        if policy is not None:
            pol = policy
        else:
            pol = "fifo" if not self.pipeline else self.policy
        with obs.span("zipflow.plan", policy=pol):
            profiles = {n: self.column_profile(n) for n in names}
            ep = planner_mod.plan_execution(
                profiles, self.cost_model, policy=pol,
                chunk_bytes=(self.chunk_bytes if chunk_bytes is self._DEFAULTS
                             else chunk_bytes),
                chunk_decode=(self.chunk_decode if chunk_decode is None
                              else chunk_decode),
                window=self.prefetch_chunks if window is None else window,
                batch_columns=self.batch_columns, fused_columns=fused_columns)
            if order is not None:
                ep = dataclasses.replace(ep, order=tuple(order),
                                         policy="explicit")
            self._count_plan(names, ep)
        return ep

    def _count_plan(self, names: Sequence[str], ep: ExecutionPlan) -> None:
        """Count a plan, and a change where its order or a column's decision
        differs from the previous plan over the same column set."""
        key = (ep.order, tuple(
            (d.name, d.chunk_bytes, d.n_chunks, d.decode_mode, d.fused)
            for _, d in sorted(ep.decisions.items())))
        cols = frozenset(names)
        if self._last_plans.get(cols, key) != key:
            obs.inc("plan_changes")
        self._last_plans[cols] = key

    # --------------------------------------------------------------------- run
    def run(self, encs: dict[str, plan_mod.Encoded] | None = None,
            order: Sequence[str] | None = None,
            plan: ExecutionPlan | None = None,
            preempt=None, on_ready=None,
            device=None,
            async_dispatch: bool | None = None) -> dict[str, ColumnExec]:
        """Transfer + decode a set of columns per an ExecutionPlan; returns
        per-column records.  Without a plan, one is built from the constructor
        defaults; measured actuals feed back into the cost model either way.

        ``preempt`` (optional, ``() -> None``) is invoked at every safe yield
        point -- between decode units and at per-chunk launch boundaries --
        so a serving layer can interleave urgent work (e.g. a point query's
        nested ``run``) into a long bulk decode without killing it: the
        outer run's staged transfers and launched chunks are all local state,
        so a nested ``run`` on the same executor composes.  ``on_ready``
        (optional, ``(name: str) -> None``) fires as soon as each column's
        output array is materialized (blocked-on) -- per-column completion
        is what per-REQUEST latency is made of when one shared run serves
        many queries' columns.  ``device`` (optional ``jax.Device``) commits
        every staged transfer to that device, so the cached programs execute
        there -- the per-device leg of a mesh plan (``run_sharded``).
        ``async_dispatch`` (None = the constructor knob) routes transfers
        through a ``DispatchEngine`` worker thread so H2D puts overlap decode
        launches; results are bitwise identical to the inline path."""
        if encs is not None:
            for name, enc in encs.items():
                if self._programs.get(name) is None or self._encoded.get(name) is not enc:
                    self.compile(name, enc)
            names = list(encs)
        else:
            names = list(self._encoded)
        with obs.root("zipflow.stream", columns=len(names)):
            return self._run(names, order, plan, preempt, on_ready, device,
                             async_dispatch)

    def _run(self, names, order, plan, preempt, on_ready, device,
             async_dispatch) -> dict[str, ColumnExec]:
        if plan is None:
            plan = self.plan(names, order=order)
        elif order is not None:
            plan = dataclasses.replace(plan, order=tuple(order),
                                       policy="explicit")
        missing = [n for n in names if n not in plan.decisions]
        if missing:
            raise ValueError(
                f"plan does not cover requested columns {missing}; it was "
                f"built over {sorted(plan.decisions)} -- re-plan after "
                "registering new columns")
        names_set = set(names)
        order = [n for n in plan.order if n in names_set]
        decisions = plan.decisions

        # host-side staging, in plan order, into ONE ordered transfer-item
        # list (the issuer's queue); decode units plus the global item index
        # each unit needs committed come back as a _StagedLeg the decode-
        # driver generator consumes.
        items: list[_TransferItem] = []
        acq: list[bool] = []
        rel: list[bool] = []
        leg = self._stage_leg(order, decisions, plan.window, items, acq, rel)
        # time spent issuing each column's device_puts: on CPU the copy happens
        # synchronously at issue; on accelerators issue is cheap and the
        # residual wait at the block is the real transfer tail -- transfer_s
        # sums both
        issue_s: dict[str, float] = {}
        use_async = (self.async_dispatch if async_dispatch is None
                     else async_dispatch)
        if not use_async:
            # inline path: puts issue synchronously from this thread at the
            # generator's advance() points -- the legacy sequential executor
            issuer = _InlineIssuer(items, device, issue_s)
            gen = self._decode_leg(leg, issuer, preempt=preempt,
                                   on_ready=on_ready)
            return _drive_seq(gen, issuer)
        engine = DispatchEngine(
            host_window=self.cost_model.topology.host_window)
        try:
            issuer = engine.issuer(items, device, issue_s, acq=acq, rel=rel)
            gen = self._decode_leg(leg, issuer, preempt=preempt,
                                   on_ready=on_ready)
            return engine.drive({0: (gen, issuer)})[0]
        finally:
            engine.close()

    def _stage_leg(self, order: Sequence[str], decisions, window: int,
                   items: list, acq: list, rel: list) -> _StagedLeg:
        """Stage one device leg's columns host-side, APPENDING to the shared
        per-link ``items``/``acq``/``rel`` lists (so a mesh device's whole
        columns and shards share one issuer queue and the recorded indices
        are global).

        Whole-mode columns split every operand row-granularly at the column's
        planned chunk size; per-chunk columns use the coordinated schedule
        (whole-resident buffers first, then chunk 0's slices, chunk 1's, ...).
        ``acq``/``rel`` mark each per-chunk-decode chunk's first/last item --
        the unit at which a transfer worker acquires / the dispatcher releases
        one shared host-staging slot (matching ``simulate_stream_multi``'s
        budget granularity; whole-mode columns hold no slots there either)."""
        scheds: dict[str, ChunkSchedule | None] = {}
        for name in order:
            d = decisions[name]
            scheds[name] = (self.chunk_schedule(name, d.chunk_bytes)
                            if d.decode_mode == planner_mod.CHUNK else None)
        staged: dict[str, dict[str, list]] = {}
        col_end: dict[str, int] = {}
        chunk_ends: dict[str, list[int]] = {}
        for name in order:
            with obs.span("zipflow.stage", column=name):
                ops = plan_mod.host_operands(self._encoded[name])
                sched = scheds[name]
                cols: dict[str, list] = {}
                staged[name] = cols
                if sched is None:
                    for k, v in ops.items():
                        pieces = split_chunks(np.asarray(v),
                                              decisions[name].chunk_bytes)
                        cols[k] = [None] * len(pieces)
                        for i, piece in enumerate(pieces):
                            items.append((name, cols[k], i, piece))
                            acq.append(False)
                            rel.append(False)
                else:
                    for k in sched.whole:
                        cols[k] = [None]
                        src = sched.host_push.get(k)
                        items.append((name, cols[k], 0, np.asarray(ops[k])
                                      if src is None else src))
                        acq.append(False)
                        rel.append(False)
                    ends = []
                    for i in range(sched.n_chunks):
                        first = len(items)
                        for k in sched.slices:
                            # group-path leaves may slice off axis 0 (ANS
                            # stripes hand each span its own row-capped
                            # column block)
                            cols.setdefault(k, [None] * sched.n_chunks)
                            piece = sched.piece(np.asarray(ops[k]), k, i)
                            items.append((name, cols[k], i, piece))
                            acq.append(False)
                            rel.append(False)
                        if len(items) > first:   # one staging slot per chunk
                            acq[first] = True
                            rel[-1] = True
                        ends.append(len(items))
                    chunk_ends[name] = ends
                col_end[name] = len(items)

        # decode units.  Per-chunk columns are singleton units (their launches
        # are already split along the chunk axis); *consecutive-in-order*
        # columns the plan marked batched-by-signature decode in a single vmap
        # launch when they share one Program.  Grouping only adjacent columns
        # keeps the transfer/decode overlap: a global group spanning the whole
        # order would force every transfer to finish before the first decode.
        # (Johnson's rule keys on (transfer, decode) times, which are equal
        # for same-signature columns, so they end up adjacent anyway.)
        units: list[tuple[str, Program | None, list[str]]] = []
        for name in order:
            if scheds[name] is not None:
                units.append(("chunk", None, [name]))
                continue
            prog = self._programs[name]
            if (decisions[name].decode_mode == planner_mod.BATCHED
                    and units and units[-1][0] == "whole"
                    and units[-1][1] is prog
                    and decisions[units[-1][2][-1]].decode_mode
                    == planner_mod.BATCHED):
                units[-1][2].append(name)
            else:
                units.append(("whole", prog, [name]))
        return _StagedLeg(decisions=decisions, scheds=scheds, staged=staged,
                          col_end=col_end, chunk_ends=chunk_ends, units=units,
                          window=window)

    def _decode_leg(self, leg: _StagedLeg, issuer, preempt=None,
                    on_ready=None):
        """Decode-driver generator for one staged leg.

        Yields ``("need", n)`` before consuming staged items < n (the driver
        -- ``_drive_seq`` or ``DispatchEngine.drive`` -- resumes it once the
        issuer has committed them, sending back the seconds it waited, 0.0
        when the wait overlapped other legs' dispatch); all tracing and
        decode launches happen on the resuming thread.  Returns the
        per-column ``ColumnExec`` dict."""
        decisions = leg.decisions
        issue_s = issuer.issue_s
        window = leg.window
        results: dict[str, ColumnExec] = {}
        for kind, prog, members in leg.units:
            if preempt is not None and results:
                preempt()                       # unit boundary: safe yield point
            if kind == "chunk":
                name = members[0]
                runner = (self._run_group_chunked
                          if leg.scheds[name].kind == "group"
                          else self._run_chunked)
                results[name] = yield from runner(
                    name, leg.scheds[name], leg.staged[name],
                    leg.chunk_ends[name], issuer, window, preempt=preempt)
                if on_ready is not None:
                    on_ready(name)
                continue
            last_end = max(leg.col_end[m] for m in members)
            issuer.advance(last_end + window)   # keep the link busy ahead of decode
            wait_s = (yield ("need", last_end)) or 0.0
            # joining a multi-piece column is staging, not a transfer wait;
            # the residual covers both, as it always has
            with obs.span("zipflow.stage", column=members[0]) as join:
                bufs_per_member = []
                for m in members:
                    chunks = leg.staged[m]
                    bufs = {k: (pieces[0] if len(pieces) == 1
                                else jnp.concatenate(pieces, axis=0))
                            for k, pieces in chunks.items()}
                    bufs_per_member.append(bufs)
            with obs.span("zipflow.wait_h2d", column=members[0],
                          chunk=0) as wait:
                for bufs in bufs_per_member:
                    jax.block_until_ready(list(bufs.values()))
            issuer.consumed(last_end)
            residual_wait = (wait_s + join.s + wait.s) / len(members)
            batched = len(members) > 1
            cold = (prog.batched_calls if batched else prog.calls) == 0
            with obs.span("zipflow.launch",
                          program="decode_batched" if batched else "decode",
                          chunk=0) as launch:
                if batched:
                    stacked = {k: jnp.stack([b[k] for b in bufs_per_member])
                               for k in bufs_per_member[0]}
                    out = prog.batched(stacked)
                else:
                    out = prog(bufs_per_member[0])
            with obs.span("zipflow.wait_decode", column=members[0]) as done:
                jax.block_until_ready(out)
            decode_s = launch.s + done.s
            if cold:      # first call traced+compiled; re-time warm so cached
                # timings model decode, not jit
                with obs.span("zipflow.retime", column=members[0]) as again:
                    jax.block_until_ready(prog.batched(stacked) if batched
                                          else prog(bufs_per_member[0]))
                decode_s = again.s
            outs = [out[i] for i in range(len(members))] if batched else [out]
            # members of one unit share a signature => identical buffer shapes and
            # bytes, so the even decode split is exact, not an approximation
            decode_s /= len(members)
            siblings = tuple(members) if len(members) > 1 else ()
            for m, arr in zip(members, outs):
                enc = self._encoded[m]
                transfer_s = issue_s.get(m, 0.0) + residual_wait
                # actuals feed the cost model's calibration loop (and, via the
                # aliased timings dict, future plans' measured jobs)
                self.cost_model.observe(m, transfer_s, decode_s)
                results[m] = ColumnExec(
                    name=m, array=arr, transfer_s=transfer_s, decode_s=decode_s,
                    compressed_bytes=enc.compressed_nbytes,
                    plain_bytes=enc.plain_nbytes,
                    n_chunks=self._n_chunks(m, decisions[m].chunk_bytes),
                    signature=self._graphs[m].signature,
                    batched_with=tuple(s for s in siblings if s != m))
                if on_ready is not None:
                    on_ready(m)
        return results

    def _run_chunked(self, name: str, sched: ChunkSchedule,
                     device_col: dict[str, list], ends: list[int],
                     issuer, window: int, preempt=None):
        """Per-chunk decode of one column: launch chunk k's decode while chunks
        k+1..k+w transfer, then concatenate the chunk outputs on device.
        Generator (see ``_decode_leg``): yields ``("need", n)`` per chunk,
        returns the ``ColumnExec``."""
        graph = self._graphs[name]
        K = sched.n_chunks
        residual = 0.0
        dispatch = 0.0
        cold = False
        whole_bufs: dict[str, jnp.ndarray] | None = None
        launches = []     # (ChunkProgram, bufs, out_start) -- kept for warm re-time
        outs = []
        for k in range(K):
            if preempt is not None and k:
                preempt()          # chunk boundary: point queries may cut in
            issuer.advance(ends[k] + window)
            residual += (yield ("need", ends[k])) or 0.0
            with obs.span("zipflow.wait_h2d", column=name, chunk=k) as wait:
                if whole_bufs is None:  # issued ahead of chunk 0 by design
                    whole_bufs = {nm: device_col[nm][0] for nm in sched.whole}
                    jax.block_until_ready(list(whole_bufs.values()))
                pieces = {nm: device_col[nm][k] for nm in sched.slices}
                jax.block_until_ready(list(pieces.values()))
            residual += wait.s
            prog = self.cache.get_chunk(graph, sched.out_sizes[k])
            cold = cold or prog.calls == 0
            bufs = {**whole_bufs, **pieces}
            start = np.int32(sched.out_starts[k])
            with obs.span("zipflow.launch", program="decode_chunk",
                          chunk=k) as launch:
                outs.append(prog(bufs, start))   # async; k+1 still in flight
            dispatch += launch.s
            issuer.consumed(ends[k])             # chunk k's staging slot frees
            launches.append((prog, bufs, start))
        with obs.span("zipflow.wait_decode", column=name) as done:
            arr = outs[0] if K == 1 else jnp.concatenate(outs)
            jax.block_until_ready(arr)
        dispatch += done.s
        if cold:      # first use traced+compiled: re-run warm so cached timings
            # model decode, not jit
            with obs.span("zipflow.retime", column=name) as again:
                outs2 = [p(b, s) for p, b, s in launches]
                jax.block_until_ready(outs2[0] if K == 1
                                      else jnp.concatenate(outs2))
            decode_s = again.s
        else:
            decode_s = dispatch
        enc = self._encoded[name]
        transfer_s = issuer.issue_s.get(name, 0.0) + residual
        self.cost_model.observe(name, transfer_s, decode_s)
        return ColumnExec(
            name=name, array=arr, transfer_s=transfer_s, decode_s=decode_s,
            compressed_bytes=enc.compressed_nbytes, plain_bytes=enc.plain_nbytes,
            n_chunks=K, signature=graph.signature,
            decode_launches=K, chunk_decoded=True)

    def _run_group_chunked(self, name: str, sched: ChunkSchedule,
                           device_col: dict[str, list], ends: list[int],
                           issuer, window: int, preempt=None,
                           observe: bool = True):
        """Group-boundary streaming decode of one column.

        The prologue (presum auxes, nested child decodes) launches once over
        the whole-resident buffers ahead of span 0; then span k's decode (a
        body or tail GroupChunkProgram over whole groups) launches while spans
        k+1..k+w are still in flight.  Launch outputs are padded to the shared
        body shape, trimmed to each span's true size and concatenated on
        device -- bitwise identical to the whole-column result.  Generator
        (see ``_decode_leg``): yields ``("need", n)`` per span, returns the
        ``ColumnExec``."""
        graph = self._graphs[name]
        K = sched.n_chunks
        residual = 0.0
        dispatch = 0.0
        cold = False
        whole_bufs: dict[str, jnp.ndarray] | None = None
        resident: dict[str, jnp.ndarray] = {}
        pro_prog = self.cache.get_group_prologue(graph)
        launches = []     # (GroupChunkProgram, bufs, args) kept for warm re-time
        outs = []
        for k in range(K):
            if preempt is not None and k:
                preempt()          # span boundary: point queries may cut in
            issuer.advance(ends[k] + window)
            residual += (yield ("need", ends[k])) or 0.0
            with obs.span("zipflow.wait_h2d", column=name, chunk=k) as wait:
                if whole_bufs is None:  # issued ahead of span 0 by design
                    whole_bufs = {nm: device_col[nm][0] for nm in sched.whole}
                    jax.block_until_ready(list(whole_bufs.values()))
                pieces = {nm: device_col[nm][k] for nm in sched.slices}
                jax.block_until_ready(list(pieces.values()))
            residual += wait.s
            if k == 0 and pro_prog is not None:
                cold = cold or pro_prog.calls == 0
                with obs.span("zipflow.launch", program="decode_prologue",
                              chunk=0) as launch:
                    resident = pro_prog(whole_bufs)  # async one-shot prologue
                dispatch += launch.s
            prog = self.cache.get_group_chunk(graph, sched.g_sizes[k],
                                              sched.pad_sizes[k])
            cold = cold or prog.calls == 0
            bufs = {**whole_bufs, **resident, **pieces}
            args = (np.int32(sched.out_starts[k]), np.int32(sched.g_starts[k]),
                    np.int32(sched.out_sizes[k]))
            with obs.span("zipflow.launch", program="decode_span",
                          chunk=k) as launch:
                outs.append(prog(bufs, *args))  # async; k+1 still in flight
            dispatch += launch.s
            issuer.consumed(ends[k])         # span k's staging slot frees
            launches.append((prog, bufs, args))
        with obs.span("zipflow.wait_decode", column=name) as done:
            trimmed = [o if int(p) == int(s) else o[:int(s)] for o, p, s
                       in zip(outs, sched.pad_sizes, sched.out_sizes)]
            arr = trimmed[0] if K == 1 else jnp.concatenate(trimmed)
            jax.block_until_ready(arr)
        dispatch += done.s
        if cold:      # first use traced+compiled: re-run warm so cached timings
            # model decode, not jit
            with obs.span("zipflow.retime", column=name) as again:
                res2 = pro_prog(whole_bufs) if pro_prog is not None else {}
                outs2 = [p({**b, **res2}, *a) for p, b, a in launches]
                outs2 = [o if int(pd) == int(s) else o[:int(s)] for o, pd, s
                         in zip(outs2, sched.pad_sizes, sched.out_sizes)]
                jax.block_until_ready(outs2[0] if K == 1
                                      else jnp.concatenate(outs2))
            decode_s = again.s
        else:
            decode_s = dispatch
        enc = self._encoded[name]
        transfer_s = issuer.issue_s.get(name, 0.0) + residual
        if observe:
            # shard-local runs skip calibration: a fraction of a column would
            # skew the per-column (transfer_s, decode_s) actuals
            self.cost_model.observe(name, transfer_s, decode_s)
        return ColumnExec(
            name=name, array=arr, transfer_s=transfer_s, decode_s=decode_s,
            compressed_bytes=enc.compressed_nbytes, plain_bytes=enc.plain_nbytes,
            n_chunks=K, signature=graph.signature,
            decode_launches=K + (1 if pro_prog is not None else 0),
            chunk_decoded=True)

    # ------------------------------------------------------------------- mesh
    def _stage_shard(self, column: str, spec, chunk_bytes: int | None,
                     items: list, acq: list, rel: list):
        """Stage one group-span shard host-side, appending its transfer items
        (whole-resident leaves first, then per-span row-capped slices) to the
        shared per-link lists; returns ``(sched, device_col, ends)`` with
        GLOBAL item indices, ready for ``_run_group_chunked``."""
        sched = self.shard_schedule(column, chunk_bytes, spec.g_lo, spec.g_hi)
        if sched is None:
            raise ValueError(f"column {column!r} is not group-span shardable")
        ops = plan_mod.host_operands(self._encoded[column])
        device_col: dict[str, list] = {}
        for nm in sched.whole:
            device_col[nm] = [None]
            src = sched.host_push.get(nm)
            items.append((column, device_col[nm], 0,
                          np.asarray(ops[nm]) if src is None else src))
            acq.append(False)
            rel.append(False)
        ends: list[int] = []
        for i in range(sched.n_chunks):
            first = len(items)
            for nm in sched.slices:
                device_col.setdefault(nm, [None] * sched.n_chunks)
                items.append((column, device_col[nm], i,
                              sched.piece(np.asarray(ops[nm]), nm, i)))
                acq.append(False)
                rel.append(False)
            if len(items) > first:   # one staging slot per span
                acq[first] = True
                rel[-1] = True
            ends.append(len(items))
        return sched, device_col, ends

    def _run_shard(self, column: str, spec, chunk_bytes: int | None,
                   device, window: int) -> ColumnExec:
        """Decode one group-span shard of a registered column on ``device``
        (inline issue -- the sequential mesh path).

        Stages the whole-resident leaves plus the span's sliced (row-capped)
        pieces committed to the target device, then delegates to the group-
        chunked runner with GLOBAL group/output offsets so the cached span
        programs decode shard-local unchanged.  Shard timings do not feed
        ``CostModel.observe`` (they would skew whole-column calibration)."""
        items: list[_TransferItem] = []
        sched, device_col, ends = self._stage_shard(column, spec, chunk_bytes,
                                                    items, [], [])
        issuer = _InlineIssuer(items, device, {})
        gen = self._run_group_chunked(column, sched, device_col, ends,
                                      issuer, window, observe=False)
        rec = _drive_seq(gen, issuer)
        return dataclasses.replace(
            rec, name=planner_mod.shard_name(column, spec.index))

    def _device_leg(self, leg: _StagedLeg | None, shard_stage: list,
                    issuer, window: int, on_ready=None, on_shard=None):
        """Combined decode-driver generator for one mesh device: the whole-
        column leg first (plan order), then each group-span shard -- exactly
        the sequence the sequential path executes per device, over ONE shared
        issuer queue.  ``on_shard(item, rec)`` fires the moment a shard's
        decode completes (the hook the D2D redistribution legs hang off, so
        fabric copies start while later shards still decode).  Returns
        ``(whole_results, shard_recs)``."""
        whole_res: dict[str, ColumnExec] = {}
        if leg is not None:
            whole_res = yield from self._decode_leg(leg, issuer,
                                                    on_ready=on_ready)
        recs = []
        for col, spec, sched, device_col, ends in shard_stage:
            rec = yield from self._run_group_chunked(
                col, sched, device_col, ends, issuer, window, observe=False)
            rec = dataclasses.replace(
                rec, name=planner_mod.shard_name(col, spec.index))
            if on_shard is not None:
                on_shard(rec.name, rec)
            recs.append((col, spec, rec))
        return whole_res, recs

    def _observe_link_actuals(self, dev_id: int, dplan: ExecutionPlan,
                              recs: Sequence[ColumnExec]) -> None:
        """Feed one device leg's measured-vs-predicted transfer ratio into the
        per-link EWMA calibration (``CostModel.observe_link``)."""
        pred = sum(d.est_transfer_s for d in dplan.decisions.values())
        meas = sum(r.transfer_s for r in recs)
        if pred > 0.0 and meas > 0.0:
            self.cost_model.observe_link(dev_id, meas / pred)

    def _observe_d2d_actual(self, nbytes: int, copy_s: float) -> None:
        """Feed one fabric copy's measured time, as a ratio over the
        calibrated H2D-equivalent for the same byte count, into the
        ``CostModel.observe_d2d`` EWMA."""
        ref = self.cost_model.h2d_equiv_s(nbytes)
        if ref > 0.0 and copy_s > 0.0:
            self.cost_model.observe_d2d(copy_s / ref)

    def _d2d_target(self, mesh_plan, devices, dst_logical: int):
        """(physical device id, jax device) for a redistribution leg's
        destination logical device."""
        dst_id = int(mesh_plan.device_ids[dst_logical])
        return dst_id, devices[dst_id]

    def _copy_shard_d2d(self, rec: ColumnExec, dst_logical: int, mesh_plan,
                        devices) -> tuple[ColumnExec, int, object, float]:
        """Move one decoded shard to its final device over the D2D fabric
        (sequential mesh path: timed, blocking ``jax.device_put``); the
        measured copy feeds the fabric EWMA."""
        dst_id, dst_dev = self._d2d_target(mesh_plan, devices, dst_logical)
        t0 = time.perf_counter()
        arr = jax.device_put(rec.array, dst_dev)
        jax.block_until_ready(arr)
        copy_s = time.perf_counter() - t0
        self._observe_d2d_actual(int(arr.nbytes), copy_s)
        return dataclasses.replace(rec, array=arr), dst_id, dst_dev, copy_s

    def run_sharded(self, mesh_plan, encs: dict[str, plan_mod.Encoded] | None = None,
                    on_ready=None, concurrent: bool | None = None
                    ) -> "MeshRunResult":
        """Execute a ``MeshExecutionPlan``: each logical device runs its
        per-device ``ExecutionPlan`` for whole columns (committed transfers,
        per-device in-flight window) plus shard-local group-span decodes;
        sharded columns assemble into one ``jax.sharding``-annotated global
        array when shard sizes are even (no host gather), falling back to
        device concatenation otherwise.

        ``concurrent`` (default: auto, on when more than one device has work)
        issues all devices' transfer streams at once -- one ``DispatchEngine``
        worker per host->device link, decode launches interleaved across
        devices from this thread as chunks commit -- instead of walking
        devices one at a time.  Results are bitwise identical either way
        (per-column sequence numbers fix chunk order; assembly is unchanged);
        measured per-link actuals feed ``CostModel.observe_link`` in both
        modes.  A plan that names a device JAX does not have raises
        ``ValueError``; it is never folded onto fewer devices."""
        if encs is not None:
            for name, enc in encs.items():
                if (self._programs.get(name) is None
                        or self._encoded.get(name) is not enc):
                    self.compile(name, enc)
        devices = jax.devices()
        missing = sorted({int(d) for d in mesh_plan.device_ids}
                         - set(range(len(devices))))
        if missing:
            raise ValueError(
                f"mesh plan names devices {missing}, but JAX has only "
                f"{len(devices)} device(s)")
        active = sum(1 for p in mesh_plan.plans if p.order)
        if concurrent is None:
            concurrent = active > 1
        if concurrent and active > 1:
            return self._run_sharded_concurrent(mesh_plan, devices, on_ready)
        per_device: dict[int, tuple[str, ...]] = {}
        device_launches: dict[int, int] = {}
        results: dict[str, ColumnExec] = {}
        shard_recs: dict[str, list] = {}
        redist_dst = {it: dst for it, _src, dst
                      in getattr(mesh_plan, "redistribution", ())}
        d2d_done: dict[str, tuple[int, int, float]] = {}
        for li, dplan in enumerate(mesh_plan.plans):
            dev_id = int(mesh_plan.device_ids[li])
            dev = devices[dev_id]
            d_items = list(dplan.order)
            per_device[dev_id] = tuple(d_items)
            launches = 0
            dev_recs: list[ColumnExec] = []
            whole = [it for it in d_items if planner_mod.SHARD_SEP not in it]
            if whole:
                res = self.run({n: self._encoded[n] for n in whole},
                               plan=dplan, on_ready=on_ready, device=dev,
                               async_dispatch=False)
                seen: set[frozenset] = set()
                for n, rec in res.items():
                    results[n] = rec
                    dev_recs.append(rec)
                    grp = frozenset((n,) + rec.batched_with)
                    if grp not in seen:     # batched members share one launch
                        seen.add(grp)
                        launches += rec.decode_launches
            for it in d_items:
                if planner_mod.SHARD_SEP not in it:
                    continue
                col = planner_mod.shard_column_of(it)
                spec = next(s for s in mesh_plan.shards[col] if s.name == it)
                rec = self._run_shard(col, spec,
                                      dplan.decisions[it].chunk_bytes,
                                      dev, dplan.window)
                launches += rec.decode_launches
                dev_recs.append(rec)
                dst = redist_dst.get(it)
                if dst is not None and int(dst) != li:
                    rec, dst_id, dst_dev, copy_s = self._copy_shard_d2d(
                        rec, int(dst), mesh_plan, devices)
                    d2d_done[it] = (dev_id, dst_id, copy_s)
                    shard_recs.setdefault(col, []).append(
                        (spec, rec, dst_id, dst_dev))
                else:
                    shard_recs.setdefault(col, []).append(
                        (spec, rec, dev_id, dev))
            device_launches[dev_id] = launches
            if d_items:
                self._observe_link_actuals(dev_id, dplan, dev_recs)
        return self._finish_sharded(results, shard_recs, per_device,
                                    device_launches, mesh_plan, on_ready,
                                    d2d_copies=d2d_done)

    def _run_sharded_concurrent(self, mesh_plan, devices,
                                on_ready=None) -> "MeshRunResult":
        """Concurrent-issue mesh execution: stage every device's leg, spawn
        one transfer worker per link (shared host-staging budget from the
        plan's topology), and drive all device legs' decode generators from
        THIS thread -- H2D streams overlap each other and every decode launch
        (all tracing stays here; workers only ``device_put``).

        Redistribution legs ride the SAME engine: each D2D copy gets its own
        single-item issuer bound to the destination device, filled via the
        ``on_shard`` hook the moment its shard's decode completes -- the
        fabric copy then runs on that worker thread, overlapping every other
        leg's remaining transfers and decodes; its blocking ``issue_s``
        records the true copy duration for ``observe_d2d``."""
        engine = DispatchEngine(
            host_window=mesh_plan.topology.host_window)
        tasks: dict[int, tuple] = {}
        legmeta: dict[int, tuple] = {}
        per_device: dict[int, tuple[str, ...]] = {}
        device_launches: dict[int, int] = {}
        redist_dst = {it: dst for it, _src, dst
                      in getattr(mesh_plan, "redistribution", ())}
        # item -> mutable D2D leg state (issuer filled at decode completion)
        d2d_legs: dict[str, dict] = {}
        d2d_done: dict[str, tuple[int, int, float]] = {}
        try:
            for li, dplan in enumerate(mesh_plan.plans):
                dev_id = int(mesh_plan.device_ids[li])
                d_items = list(dplan.order)
                per_device[dev_id] = tuple(d_items)
                device_launches[dev_id] = 0
                if not d_items:
                    continue
                dev = devices[dev_id]
                items: list[_TransferItem] = []
                acq: list[bool] = []
                rel: list[bool] = []
                whole = [it for it in d_items
                         if planner_mod.SHARD_SEP not in it]
                leg = (self._stage_leg(whole, dplan.decisions, dplan.window,
                                       items, acq, rel) if whole else None)
                shard_stage = []
                for it in d_items:
                    if planner_mod.SHARD_SEP not in it:
                        continue
                    col = planner_mod.shard_column_of(it)
                    spec = next(s for s in mesh_plan.shards[col]
                                if s.name == it)
                    sched, device_col, ends = self._stage_shard(
                        col, spec, dplan.decisions[it].chunk_bytes,
                        items, acq, rel)
                    shard_stage.append((col, spec, sched, device_col, ends))
                    dst = redist_dst.get(it)
                    if dst is not None and int(dst) != li:
                        dst_id, dst_dev = self._d2d_target(mesh_plan, devices,
                                                           int(dst))
                        # placeholder item: the worker never reads it until
                        # on_shard fills the slot and advances the watermark
                        d_items_list: list = [None]
                        d_times: dict[str, float] = {}
                        d2d_legs[it] = {
                            "items": d_items_list, "dest": [None],
                            "times": d_times, "src_id": dev_id,
                            "dst_id": dst_id, "dst_dev": dst_dev,
                            "filled": False,
                            "iss": engine.issuer(
                                d_items_list, dst_dev, d_times,
                                acq=[False], rel=[False],
                                name=f"zipflow-d2d-{it}", sync=True)}

                def on_shard(item, rec, _legs=d2d_legs):
                    ent = _legs.get(item)
                    if ent is not None:
                        ent["items"][0] = (item, ent["dest"], 0, rec.array)
                        ent["filled"] = True
                        ent["iss"].advance(1)

                iss = engine.issuer(items, dev, {}, acq=acq, rel=rel,
                                    name=f"zipflow-xfer-d{dev_id}")
                gen = self._device_leg(leg, shard_stage, iss, dplan.window,
                                       on_ready=on_ready, on_shard=on_shard)
                tasks[li] = (gen, iss)
                legmeta[li] = (dev_id, dev, dplan)
            done = engine.drive(tasks)
            for it, ent in d2d_legs.items():
                if ent["filled"]:
                    ent["iss"].wait(1)
                    d2d_done[it] = (ent["src_id"], ent["dst_id"],
                                    ent["times"].get(it, 0.0))
        finally:
            engine.close()
        results: dict[str, ColumnExec] = {}
        shard_recs: dict[str, list] = {}
        for li, (dev_id, dev, dplan) in legmeta.items():
            whole_res, recs = done[li]
            launches = 0
            seen: set[frozenset] = set()
            for n, rec in whole_res.items():
                results[n] = rec
                grp = frozenset((n,) + rec.batched_with)
                if grp not in seen:         # batched members share one launch
                    seen.add(grp)
                    launches += rec.decode_launches
            for col, spec, rec in recs:
                launches += rec.decode_launches
                ent = d2d_legs.get(rec.name)
                if ent is not None and ent["filled"]:
                    copied = ent["dest"][0]
                    self._observe_d2d_actual(int(copied.nbytes),
                                             ent["times"].get(rec.name, 0.0))
                    shard_recs.setdefault(col, []).append(
                        (spec, dataclasses.replace(rec, array=copied),
                         ent["dst_id"], ent["dst_dev"]))
                else:
                    shard_recs.setdefault(col, []).append(
                        (spec, rec, dev_id, dev))
            device_launches[dev_id] = launches
            self._observe_link_actuals(
                dev_id, dplan,
                list(whole_res.values()) + [r for _, _, r in recs])
        return self._finish_sharded(results, shard_recs, per_device,
                                    device_launches, mesh_plan, on_ready,
                                    d2d_copies=d2d_done)

    def _finish_sharded(self, results: dict, shard_recs: dict,
                        per_device: dict, device_launches: dict,
                        mesh_plan, on_ready=None,
                        d2d_copies: dict | None = None) -> "MeshRunResult":
        """Assemble shard outputs (shared by both mesh issue modes).  Shard
        tuples carry their FINAL device (redistributed shards arrive already
        copied), so the assembled ``NamedSharding`` reflects the plan's
        requested placement, not where the bytes landed."""
        for col in sorted(shard_recs):
            lst = sorted(shard_recs[col], key=lambda t: t[0].index)
            recs = [t[1] for t in lst]
            arr = self._assemble_shards([r.array for r in recs],
                                        [t[3] for t in lst])
            enc = self._encoded[col]
            results[col] = ColumnExec(
                name=col, array=arr,
                transfer_s=max(r.transfer_s for r in recs),
                decode_s=max(r.decode_s for r in recs),
                compressed_bytes=enc.compressed_nbytes,
                plain_bytes=enc.plain_nbytes,
                n_chunks=sum(r.n_chunks for r in recs),
                signature=self._graphs[col].signature,
                decode_launches=sum(r.decode_launches for r in recs),
                chunk_decoded=True,
                shard_devices=tuple(t[2] for t in lst))
            if on_ready is not None:
                on_ready(col)
        return MeshRunResult(columns=results, per_device=per_device,
                             device_launches=device_launches, plan=mesh_plan,
                             d2d_copies=dict(d2d_copies or {}))

    @staticmethod
    def _assemble_shards(arrs: list, devs: list):
        """Join shard outputs into one global array.  Equal-size shards on
        distinct devices join zero-copy via
        ``jax.make_array_from_single_device_arrays`` over a 1-axis mesh, so
        the result is already sharding-annotated for a sharded consumer;
        uneven or co-located shards fall back to device concatenation."""
        if len(arrs) == 1:
            return arrs[0]
        sizes = [int(a.shape[0]) for a in arrs]
        if len(set(sizes)) == 1 and len(set(devs)) == len(devs):
            from jax.sharding import Mesh, NamedSharding, PartitionSpec
            mesh = Mesh(np.array(devs), ("shard",))
            sharding = NamedSharding(mesh, PartitionSpec("shard"))
            gshape = (sum(sizes),) + tuple(arrs[0].shape[1:])
            singles = [jax.device_put(a, d) for a, d in zip(arrs, devs)]
            return jax.make_array_from_single_device_arrays(
                gshape, sharding, singles)
        return jnp.concatenate([jax.device_put(a, devs[0]) for a in arrs])

    # ------------------------------------------------------------- fused query
    def run_query(self, fq, encs: dict[str, plan_mod.Encoded] | None = None,
                  chunk_bytes: int | None | object = _DEFAULTS,
                  window: int | None = None) -> "QueryExec":
        """Execute a decode-fused query (``core.query.lower_query`` output).

        Non-fusible (resident) columns decode first through the normal planned
        ``run`` path; then ONE shared row-axis chunk schedule streams every
        fused column's leaf buffers together, and each chunk launches the
        cached ``QueryChunkProgram`` -- scan-filter-aggregate fused into the
        decode launch.  Each launch returns a partial-aggregate vector
        (``graph.n_out`` lanes) summed into an on-device accumulator; the
        decompressed columns never exist in HBM.  The accumulator itself holds
        one in-flight staging slot, so the effective transfer window is
        ``max(1, window - 1)``.  Measured selectivity (the Reduce count lane)
        feeds the cost model's per-signature EWMA for future fused-vs-
        materialize planning."""
        with obs.root("zipflow.query", query=fq.qplan.name):
            return self._run_query(fq, encs, chunk_bytes, window)

    def _run_query(self, fq, encs, chunk_bytes, window) -> "QueryExec":
        from repro.core import fusion
        from repro.core.ir import query_chunk_layout

        if chunk_bytes is self._DEFAULTS:
            chunk_bytes = self._fixed_chunk_bytes
        resident_execs: dict[str, ColumnExec] = {}
        res_bufs: dict[str, jnp.ndarray] = {}
        if fq.resident:
            missing = [c for c in fq.resident if not encs or c not in encs]
            if missing:
                raise ValueError(
                    f"resident columns need their Encoded blobs: {missing}")
            resident_execs = self.run({c: encs[c] for c in fq.resident})
            for c in fq.resident:
                res_bufs[fq.resident_input(c)] = resident_execs[c].array

        graph = fq.graph
        n, ops = fq.n_rows, fq.operands
        # shared row-axis schedule over the fused columns' tiled leaves --
        # the same leaf addressing _build_schedule uses, resolved against
        # THIS query's merged operand set; memoized per (structure, chunking)
        # so warm calls go straight to staging
        skey = (graph.signature,
                None if chunk_bytes is None else int(chunk_bytes), n)
        sched = self._query_schedules.get(skey)
        if sched is None:
            layout = query_chunk_layout(graph)
            if layout is None:
                raise ValueError(
                    f"graph {graph.nesting!r} is not query-chunkable")
            ratios: dict[str, tuple[int, int]] = {}
            per_elem = 0.0
            for nm, spec in layout.tiled.items():
                num = int(ops[spec.num_op][0]) if spec.num_op else int(spec.num)
                ratios[nm] = (num, int(spec.den))
                per_elem += num / spec.den * np.dtype(ops[nm].dtype).itemsize
            chunk_elems = (n if chunk_bytes is None
                           else costmodel.aligned_chunk_elems(
                               chunk_bytes, per_elem, layout.align))
            chunk_elems = min(chunk_elems, n)
            out_starts = tuple(range(0, n, chunk_elems))
            out_sizes = tuple(min(chunk_elems, n - s) for s in out_starts)
            host_slices: list[dict[str, tuple[int, int]]] = []
            for s, sz in zip(out_starts, out_sizes):
                sl = {}
                for nm, (num, den) in ratios.items():
                    length = int(np.asarray(ops[nm]).shape[0])
                    lo = (s * num) // den
                    hi = length if s + sz >= n else ((s + sz) * num) // den
                    sl[nm] = (lo, max(hi, lo + 1))
                host_slices.append(sl)
            sched = (tuple(layout.whole), out_starts, out_sizes, host_slices)
            self._query_schedules[skey] = sched
        whole_names, out_starts, out_sizes, host_slices = sched
        K = len(out_starts)

        t_issue = 0.0
        qname = fq.qplan.name
        scan = obs.current_scan()

        def put_group(pieces: dict[str, np.ndarray]) -> dict[str, jnp.ndarray]:
            # ONE batched device_put per staging group: per-call dispatch
            # overhead, not bytes, dominates small-buffer H2D
            nonlocal t_issue
            keys = list(pieces)
            nbytes = sum(int(p.nbytes) for p in pieces.values())
            with obs.span("zipflow.put", column=qname, bytes=nbytes,
                          scan=scan) as put:
                outs = jax.device_put([pieces[nm] for nm in keys])  # async
            t_issue += put.s
            return dict(zip(keys, outs))

        whole_bufs = put_group({nm: np.asarray(ops[nm]) for nm in whole_names})
        # the on-device partial-aggregate accumulator holds one staging slot
        win = 2 if window is None else max(1, int(window))
        eff = max(1, win - 1)
        device_pieces: list[dict[str, jnp.ndarray] | None] = [None] * K
        next_issue = 0

        def issue_upto(m: int) -> None:
            nonlocal next_issue
            while next_issue < min(m, K):
                with obs.span("zipflow.stage", column=qname, chunk=next_issue):
                    pieces = {nm: np.asarray(ops[nm])[lo:hi] for nm, (lo, hi)
                              in host_slices[next_issue].items()}
                device_pieces[next_issue] = put_group(pieces)
                next_issue += 1

        residual = 0.0
        dispatch = 0.0
        cold = False
        launches = []      # (QueryChunkProgram, bufs, start) for warm re-time
        acc = None
        for k in range(K):
            issue_upto(k + eff)
            with obs.span("zipflow.wait_h2d", column=qname, chunk=k) as wait:
                if k == 0:
                    jax.block_until_ready(list(whole_bufs.values()))
                pieces = device_pieces[k]
                jax.block_until_ready(list(pieces.values()))
            residual += wait.s
            prog = self.cache.get_query_chunk(graph, out_sizes[k])
            cold = cold or prog.calls == 0
            bufs = {**whole_bufs, **res_bufs, **pieces}
            start = np.int32(out_starts[k])
            with obs.span("zipflow.launch", program="query_chunk",
                          chunk=k) as launch:
                part = prog(bufs, start)      # async launch; k+1.. in flight
                acc = part if acc is None else acc + part
            dispatch += launch.s
            launches.append((prog, bufs, start))
        with obs.span("zipflow.wait_decode", column=qname) as done:
            jax.block_until_ready(acc)
        dispatch += done.s
        if cold:      # first use traced+compiled: re-run warm so timings model
            # the fused decode, not jit
            with obs.span("zipflow.retime", column=qname) as again:
                acc2 = None
                for p, b, s in launches:
                    part = p(b, s)
                    acc2 = part if acc2 is None else acc2 + part
                jax.block_until_ready(acc2)
            decode_s = again.s
            acc = acc2
        else:
            decode_s = dispatch
        transfer_s = t_issue + residual

        with obs.span("zipflow.finalize", query=qname):
            # acc is tiny (lanes x segments): one D2H pull serves selectivity
            # and the finalized result without extra device slicing round-trips
            acc_np = np.asarray(acc)
            sel = float(fq.selectivity(acc_np))
            for c in fq.fused_cols:
                if c not in self.cost_model.profiles and encs and c in encs:
                    from repro.core.compiler import build_graph
                    self.cost_model.register(
                        profile_from(c, encs[c], build_graph(encs[c])))
                if c in self.cost_model.profiles:
                    self.cost_model.observe_selectivity(c, sel)
            traffic = self._query_traffic.get(graph.signature)
            if traffic is None:
                all_bufs = {**ops, **res_bufs}
                traffic = (fusion.hbm_traffic_bytes(graph.stages, all_bufs),
                           fusion.hbm_traffic_bytes(fq.prefuse_stages, all_bufs))
                self._query_traffic[graph.signature] = traffic
            compressed = sum(int(np.asarray(ops[b.name]).nbytes)
                             for b in graph.buffers)
            plain = (sum(int(encs[c].plain_nbytes) for c in fq.fused_cols)
                     if encs else 0)
            return QueryExec(
                name=fq.qplan.name, result=fq.finalize(acc_np), acc=acc,
                transfer_s=transfer_s, decode_s=decode_s,
                n_chunks=K, decode_launches=K, selectivity=sel,
                compressed_bytes=compressed, plain_bytes=plain,
                traffic_bytes=traffic[0], prefuse_traffic_bytes=traffic[1],
                resident=resident_execs)

    def unregister(self, name: str) -> None:
        """Drop one registered blob's per-column state (profile, schedules,
        measured timings).  Compiled programs stay in the shared ProgramCache,
        and the cost model's per-SIGNATURE history survives -- so a long-lived
        server keeps its calibration while per-request names come and go."""
        for store in (self._encoded, self._graphs, self._programs):
            store.pop(name, None)
        for store in (self._chunk_counts, self._schedules):
            for key in [k for k in store if k[0] == name]:
                store.pop(key)
        for cols in [c for c in self._last_plans if name in c]:
            self._last_plans.pop(cols)
        self.cost_model.forget(name)

    def run_one(self, enc: plan_mod.Encoded, name: str = "_single") -> jnp.ndarray:
        """Decode a single blob through the cache (serving-path helper).

        The blob is unregistered afterwards so a long-lived engine serving many
        requests does not accumulate per-request state; compiled programs stay in
        the shared ProgramCache."""
        self.compile(name, enc)
        try:
            return self.run({name: enc})[name].array
        finally:
            self.unregister(name)

    # ------------------------------------------------------------------- model
    def measured_jobs(self, names: Sequence[str] | None = None) -> list[scheduler.Job]:
        """Scheduling jobs from the cost model, in CONSISTENT units: measured
        wall-clock when every column has a measurement, EWMA-calibrated chip
        estimates for all otherwise (see ``CostModel.jobs``)."""
        names = list(self._encoded) if names is None else list(names)
        return self.cost_model.jobs(names)

    def modeled_makespan(self, names: Sequence[str] | None = None,
                         pipeline: bool = True, johnson: bool = True,
                         chunked: bool = False) -> float:
        """Two-machine flow-shop makespan from current (measured or estimated)
        per-column times, optionally at chunk granularity."""
        jobs = self.measured_jobs(names)
        if not pipeline:
            return scheduler.serial_time(jobs)
        if chunked:
            jobs = scheduler.chunk_jobs(jobs, [self._n_chunks(j.name)
                                               for j in jobs])
        order = (scheduler.johnson_order(jobs) if johnson
                 else scheduler.fifo_order(jobs))
        return scheduler.makespan(jobs, order)
