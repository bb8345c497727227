"""Compressed host->device data pipeline (the paper's end-to-end workflow, Fig. 3,
integrated into LM training).

``CompressedTokenLoader`` stores/ships token batches bit-packed to ceil(log2 vocab)
bits with a *fixed* bit width, so every step's compressed buffers have identical
shapes -- the decode prologue jits once and the decompression fuses into the train
step (overlapping the previous step's compute, the Pipelining Layer's role inside one
program).

``ColumnPipeline`` is the analytics-shaped pipeline: arbitrary per-column plans,
Johnson's-rule issue ordering across columns (paper §3.3), async ``device_put`` so
transfer of column k+1 overlaps decode of column k.
"""
from __future__ import annotations

import math
import time
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import compiler, plan as plan_mod
from repro.core.executor import ColumnExec, StreamingExecutor
from repro.core.plan import Plan, make_plan


# ------------------------------------------------------------- training loader

class CompressedTokenLoader:
    """Wraps a token source with fixed-width bit-packed transfer."""

    def __init__(self, vocab: int, batch: int, seq_len: int,
                 source: Callable[[int], np.ndarray] | None = None,
                 seed: int = 0):
        self.vocab = vocab
        self.batch = batch
        self.seq = seq_len
        self.bits = max(1, math.ceil(math.log2(max(vocab, 2))))
        self._rng = np.random.default_rng(seed)
        self._source = source or self._synthetic
        self.bytes_plain = 0
        self.bytes_compressed = 0

    def _synthetic(self, step: int) -> np.ndarray:
        rng = np.random.default_rng(step)  # deterministic in step (FT requirement)
        return rng.integers(0, self.vocab, (self.batch, self.seq + 1),
                            dtype=np.int32)

    def encode_host(self, step: int) -> dict[str, np.ndarray]:
        """Host side: tokens -> fixed-shape packed words."""
        from repro.algos.bitpack import pack_np

        toks = self._source(step)
        packed = pack_np(toks.reshape(-1).astype(np.int64), self.bits)
        self.bytes_plain += toks.nbytes
        self.bytes_compressed += packed.nbytes
        return {"packed": packed}

    def decode_fn(self):
        """Jittable device prologue: packed words -> {tokens, labels}."""
        from repro.kernels.ref import unpack_bits_ref

        B, S, bits = self.batch, self.seq, self.bits

        def decode(bufs):
            flat = unpack_bits_ref(bufs["packed"], B * (S + 1), bits)
            toks = flat.reshape(B, S + 1).astype(jnp.int32)
            return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

        return decode

    def batches(self, start_step: int = 0) -> Iterator[dict[str, jnp.ndarray]]:
        step = start_step
        while True:
            yield {k: jax.device_put(v) for k, v in self.encode_host(step).items()}
            step += 1

    @property
    def ratio(self) -> float:
        return self.bytes_plain / max(self.bytes_compressed, 1)


# ------------------------------------------------------------ analytics pipeline

# the executor's per-column record (name/array/transfer_s/decode_s/compressed_bytes/
# plain_bytes + n_chunks/signature/batched_with) IS the pipeline's result type
ColumnResult = ColumnExec


class ColumnPipeline:
    """Transfer + decompress a set of columns through the streaming executor.

    Columns flow Plan -> DecodeGraph -> ProgramCache -> planner ->
    StreamingExecutor: one jit per column *structure* (data-dependent meta rides
    as runtime operands), and every scheduling decision (issue order, per-column
    chunk size, decode mode, in-flight window) comes from an ``ExecutionPlan``
    built by ``core/planner.py`` under the configured ``policy`` ("fifo",
    "johnson", "chunk-johnson", or "adaptive" with ``chunk_bytes="auto"`` for
    per-column sizing).  Same-signature columns decode in one batched launch.
    ``chunk_decode=True`` additionally
    launches one decode per transferred chunk for element-chunkable columns, so
    transfer/decode overlap *within* a column (the measured counterpart of the
    ``Zc`` chunk-level makespan model).  Per-column (transfer_s, decode_s)
    measurements are cached on the instance -- ``run`` and ``modeled_makespan``
    reuse the executor's timings instead of re-transferring and re-decoding every
    column per call.  ``cost_model`` lets a persisted model (``CostModel.load``)
    seed planning from a previous process's calibrated history.
    """

    def __init__(self, plans: dict[str, Plan], backend: str = "jnp",
                 fuse: bool = True, pipeline: bool = True,
                 chunk_bytes: int | None | str = 1 << 20,
                 batch_columns: bool = True, chunk_decode: bool = False,
                 policy: str = "chunk-johnson",
                 executor: StreamingExecutor | None = None,
                 cost_model=None, mesh: int | None = None,
                 async_dispatch: bool = False, placement: str | None = None):
        self.plans = plans
        # mesh=N enables topology-aware multi-device planning: run_sharded()
        # partitions columns (and group-span shards) over N devices, and
        # run_query() row-shards a fused query over N chips;
        # placement="sharded" pins each shard's FINAL device so the planner
        # may land bytes elsewhere and rebalance over the D2D fabric tier
        self.mesh = mesh
        self.placement = placement
        # async_dispatch=True moves host->device puts onto a per-link transfer
        # worker thread (core.executor.DispatchEngine) so issuance overlaps
        # decode dispatch instead of blocking between launches
        self.executor = executor or StreamingExecutor(
            backend=backend, fuse=fuse, chunk_bytes=chunk_bytes,
            pipeline=pipeline, batch_columns=batch_columns,
            chunk_decode=chunk_decode, policy=policy, cost_model=cost_model,
            async_dispatch=async_dispatch)
        # mirror the *effective* config (an explicitly passed executor wins)
        self.backend = self.executor.backend
        self.fuse = self.executor.fuse
        self.pipeline = self.executor.pipeline
        self.chunk_bytes = self.executor.chunk_bytes
        self.chunk_decode = self.executor.chunk_decode
        self.policy = self.executor.policy
        self.async_dispatch = self.executor.async_dispatch
        self._encoded: dict[str, plan_mod.Encoded] = {}
        self._decoders: dict[str, compiler.Program] = {}
        # lowered fused queries + planned (window, chunk_bytes), keyed by
        # QueryPlan digest (invalidated by compress: new blobs re-lower)
        self._queries: dict[str, tuple] = {}
        self._query_cfg: dict[str, tuple[int, int | None]] = {}

    @property
    def _timings(self) -> dict[str, tuple[float, float]]:
        """Single store for measurements: the executor's timing dict (executor.compile
        invalidates entries when a name is re-registered with new data)."""
        return self.executor.timings

    def compress(self, columns: dict[str, np.ndarray]) -> dict[str, float]:
        ratios = {}
        for name, arr in columns.items():
            enc = plan_mod.encode(self.plans[name], arr)
            self._encoded[name] = enc
            self._decoders[name] = self.executor.compile(name, enc)
            ratios[name] = enc.ratio
        self._queries.clear()
        self._query_cfg.clear()
        return ratios

    @property
    def cache_stats(self) -> dict[str, int]:
        """ProgramCache counters: how many distinct programs served the columns."""
        return self.executor.cache.stats

    def _measure(self, name: str) -> tuple[float, float]:
        """Cached (transfer_s, decode_s) for scheduling: reuses executor timings
        from the latest ``run``; measures at most once otherwise."""
        if name in self._timings:
            return self._timings[name]
        enc = self._encoded[name]
        prog = self._decoders[name]
        t0 = time.perf_counter()
        bufs = compiler.device_buffers(enc)
        jax.block_until_ready(list(bufs.values()))
        transfer_s = time.perf_counter() - t0
        if prog.calls == 0:       # discard the trace+XLA-compile call: cached
            jax.block_until_ready(prog(bufs))   # timings model decode, not jit
        t1 = time.perf_counter()
        out = prog(bufs)
        jax.block_until_ready(out)
        # through observe(), not the raw dict: the measurement must also feed
        # the cost model's EWMA calibration, like the executor's own actuals
        self.executor.cost_model.observe(name, transfer_s,
                                         time.perf_counter() - t1)
        return self._timings[name]

    def plan(self, policy: str | None = None, **kw):
        """Build an ``ExecutionPlan`` over the registered columns (planner layer;
        measured timings when a ``run`` has happened, calibrated chip estimates
        otherwise).  Keyword overrides pass through to ``StreamingExecutor.plan``
        (``chunk_bytes="auto"`` enables per-column chunk sizing)."""
        return self.executor.plan(list(self._encoded), policy=policy, **kw)

    def run(self, order: list[str] | None = None,
            plan=None) -> dict[str, ColumnResult]:
        """Execute the pipeline under an ExecutionPlan (auto-built from the
        configured policy unless given; an explicit ``order`` pins issue order).

        The first run of fresh columns plans from the calibrated chip-model
        estimate (no pre-run profiling pass -- the old behaviour of
        transferring+decoding every column once just to schedule it is exactly
        the double-measurement this replaces); runs after a ``run`` or
        ``_measure`` plan from measured timings.
        """
        return self.executor.run(self._encoded, order=order, plan=plan)

    def mesh_plan(self, n_devices: int | None = None, **kw):
        """Topology-aware ``MeshExecutionPlan`` over the registered columns
        (``planner.plan_mesh_execution``): whole columns -- and group-span
        shards of oversized ones -- assigned to ``n_devices`` links so the
        modeled ``simulate_stream_multi`` makespan is <= round-robin and
        single-device by construction.  Defaults to the constructor's
        ``mesh=`` count (else every visible jax device)."""
        from repro.core import planner as planner_mod

        n = n_devices if n_devices is not None else self.mesh
        if n is None:
            n = len(jax.devices())
        profiles = {name: self.executor.column_profile(name)
                    for name in self._encoded}
        kw.setdefault("chunk_bytes", self.chunk_bytes)
        kw.setdefault("policy", self.policy)
        kw.setdefault("placement", self.placement)
        return planner_mod.plan_mesh_execution(
            profiles, self.executor.cost_model, n_devices=n, **kw)

    def run_sharded(self, n_devices: int | None = None, plan=None):
        """Execute the registered columns over a device mesh (per-device
        in-flight windows, shard-local decode; sharded outputs land
        ``jax.sharding``-annotated).  Returns ``executor.MeshRunResult``."""
        if plan is None:
            plan = self.mesh_plan(n_devices)
        return self.executor.run_sharded(plan, self._encoded)

    def lower_query(self, qplan):
        """Graft a ``core.query.QueryPlan`` onto the registered columns' decode
        graphs (``FusedQuery``); the blobs used are the ones ``compress`` built.
        Lowerings are memoized by query digest (``compress`` invalidates), so
        warm ``run_query`` calls measure execution, not re-lowering."""
        key = qplan.digest()
        hit = self._queries.get(key)
        if hit is None:
            from repro.core.query import lower_query

            encs = {c: self._encoded[c] for c in qplan.columns()}
            hit = (lower_query(qplan, encs), encs)
            self._queries[key] = hit
        return hit

    def query_plan(self, qplan, **kw):
        """ExecutionPlan for a pending query: per column, fused-vs-materialize
        decided by the cost model's selectivity-aware fused estimate
        (``plan.explain()`` shows ``mode=...+fused sel=...`` rows)."""
        fq, encs = self.lower_query(qplan)
        return self.executor.plan(list(encs),
                                  fused_columns={c: None for c in fq.fused_cols},
                                  **kw)

    def run_query(self, qplan, window: int | None = None):
        """Decode-fused query execution (late materialization): stream the
        fused columns through per-chunk scan-filter-aggregate launches; only
        partial aggregates reach HBM.  The in-flight window AND the row-chunk
        count come from the cost model (memoized per query digest): the fused
        columns form ONE shared-schedule job, and the chunk count is chosen by
        ``planner.query_chunk_bytes`` over a small ladder, pricing each extra
        launch at the calibrated overhead — on hosts where launch overhead
        dominates (CPU) this collapses to a single fused launch; where
        transfer/decode overlap pays, it chunks.  An explicitly configured
        fixed ``chunk_bytes`` overrides the search, like ``run``.  The fused
        accumulator costs one staging slot, accounted inside
        ``StreamingExecutor.run_query``.

        With ``mesh=N`` (N > 1) the query is row-sharded over N chips
        (``StreamingExecutor.run_query``): the search prices each chip's
        share of the job on its own host link, so the chunk size is a
        chip's, and the resident columns are row shards placed by chip
        whatever ``placement`` says."""
        from repro.core import planner

        fq, encs = self.lower_query(qplan)
        key = qplan.digest()
        cfg = self._query_cfg.get(key)
        if cfg is None:
            with obs.span("zipflow.plan", policy=self.policy):
                ep = self.query_plan(qplan)  # registers profiles for all cols
                if isinstance(self.chunk_bytes, int):
                    cb = self.chunk_bytes       # fixed size: user override
                else:
                    cm = self.executor.cost_model
                    t_tr = d_fused = oh = 0.0
                    for c in fq.fused_cols:
                        t_tr += cm.predict(c)[0]
                        d_fused += cm.fused_decode_s(c)
                        oh = max(oh, cm.launch_overhead_s(c))
                    scales = (1.0,)
                    if self.mesh is not None and self.mesh > 1:
                        scales = tuple(cm.topology.scale(d)
                                       for d in range(self.mesh))
                    comp = sum(self._encoded[c].compressed_nbytes
                               for c in fq.fused_cols)
                    cb = planner.query_chunk_bytes(
                        qplan.name, comp, t_tr, d_fused, oh, ep.window,
                        link_scales=scales)
                cfg = (ep.window, cb)
                self._query_cfg[key] = cfg
        win, cb = cfg
        if window is not None:
            win = window
        return self.executor.run_query(fq, encs, chunk_bytes=cb, window=win,
                                       mesh=self.mesh)

    def modeled_makespan(self, pipeline: bool = True, johnson: bool = True,
                         chunked: bool = False) -> float:
        """Two-machine flow-shop makespan from cached per-column times (chunk-level
        jobs when ``chunked``); measures each column at most once, ever."""
        names = list(self._encoded)
        for n in names:
            self._measure(n)
        return self.executor.modeled_makespan(
            names=names, pipeline=pipeline, johnson=johnson, chunked=chunked)

    def serve_planner(self, policy: str = "shared",
                      max_wave: int | None = None,
                      mesh: int | None = None):
        """Multi-query serving planner sharing this pipeline's executor (and
        therefore its ProgramCache and calibrated CostModel): concurrent
        requests' columns compose into one shared transfer queue, with
        cross-request signature batching and SLO-aware issue ordering
        (``core/serve_planner.py``).  Requests submit their own ``Encoded``
        blobs; ``encode_request`` builds one from this pipeline's plans."""
        from repro.core.serve_planner import ServePlanner

        return ServePlanner(self.executor, policy=policy, max_wave=max_wave,
                            mesh=mesh if mesh is not None else self.mesh)

    def encode_request(self, columns: dict[str, np.ndarray]
                       ) -> dict[str, plan_mod.Encoded]:
        """Encode a request's columns with this pipeline's per-column plans
        (serving-path helper: blobs for ``ServePlanner.submit``)."""
        return {name: plan_mod.encode(self.plans[name], arr)
                for name, arr in columns.items()}
