"""The three ZipFlow parallel patterns (paper §3.1) as a small stage IR.

A decompression *plan* lowers to a list of stages over named buffers:

  * ``FullyParallel`` -- out[i] = fn(i, inputs...), no cross-element dependency.
  * ``GroupParallel`` -- variable-sized groups expand 1->N; out[i] is produced from the
    group g owning position i and the within-group offset pos = i - presum[g].
  * ``NonParallel``   -- chunked serial decode (ANS): lanes decode independent chunks in
    lockstep; see ``repro.algos.ans``.
  * ``Aux``           -- whole-array auxiliary ops (cumsum, exception scatter), the
    paper's "PyTorch out-of-the-box operations" escape hatch (§3.2, Fig. 7).

Each stage can be executed by three backends (``repro.core.compiler``): pure-jnp
(reference), Pallas TPU kernels (production; interpret=True on CPU), and an unfused
"baseline" emulating a fixed-schedule library (the nvCOMP role in the paper).

The per-element functions (``fn``, ``map_fn``) are jnp-traceable closures over *vectors*
of elements, so the very same closure is inlined into Pallas kernel bodies by the fusion
pass -- this is the TPU analogue of the paper's kernel fusion (§3.2, Fig. 7(c)).

Data-dependent scalar metadata (bitpack ``bit_width``/``base``, delta ``base``) is NOT
closed over: it arrives as extra (1,)-shaped *operand* inputs listed in ``inputs`` with
``BufSpec("full")``, so one traced program serves every blob that shares the structure
(see ``repro.core.ir.MetaSpec``).  Each stage also declares its **chunkability** --
which output boundaries it can be split at -- which the streaming executor uses to
decide between per-chunk decode launches and one whole-column launch.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax.numpy as jnp
import numpy as np


# --- chunkability levels (what output boundaries a stage can be split at) ---
# FullyParallel stages evaluate out[i] independently, so any element boundary works;
# GroupParallel can only split where whole groups do (data-dependent boundaries);
# NonParallel is serial *within* an ANS chunk but its chunks are mutually
# independent, so it also splits at group (= ANS chunk) boundaries; Aux
# (whole-array ops) only decodes whole buffers.  The streaming executor uses these
# declarations (via ``ir.element_chunk_layout`` / ``ir.group_chunk_layout``) to
# pick a per-chunk decode path or fall back to one whole-column launch.
CHUNK_ELEMENT = "element"
CHUNK_GROUP = "group"
CHUNK_NONE = "none"

# BufSpec kinds understood by the chunk planners:
#   "tile" -- sliced proportionally to the output tile (num/den ratio);
#   "full" -- whole buffer resident (small metadata, lifted operands);
#   "row"  -- a *decoded* column resident on device, gathered at the global
#             output row index (fused-query inputs that could not be fused,
#             e.g. an ANS-coded column feeding a group-by key).



@dataclasses.dataclass(frozen=True)
class BufSpec:
    """How an input buffer is tiled relative to the output tile.

    kind="tile": the block covering output range [o0, o1) is input range
                 [o0*num//den, o1*num//den) (+pad guard words); bitpack uses den=32
                 on uint32 words.  kind="full": whole buffer resident in VMEM
                 (small metadata: dictionaries, tables).

    ``num_op`` names a runtime meta operand (a (1,) buffer in the stage's inputs)
    that supplies ``num`` at execution time -- e.g. bitpack's data-dependent
    ``bit_width``.  A dynamic ratio cannot drive static kernel windowing, so the
    Pallas backends keep such buffers whole-resident; host-side chunk planning
    resolves the operand's value per blob and slices exactly.
    """

    kind: str = "tile"  # "tile" | "full" | "row"
    num: int = 1
    den: int = 1
    pad: int = 0        # extra trailing elements fetched (cross-word guard)
    num_op: str = ""    # env name of the runtime operand supplying num ("" = static)


@dataclasses.dataclass
class Ctx:
    """Execution context handed to per-element closures.

    out_idx: global output indices of the elements being produced (int32 vector).
    starts:  global start offset of each input block (0 for the jnp backend, the block
             origin inside Pallas kernels).
    """

    out_idx: jnp.ndarray
    starts: tuple[Any, ...] = ()


class Stage:
    out: str
    n_out: int
    out_dtype: Any
    chunkability = CHUNK_NONE   # overridden per pattern (not a dataclass field)


def primary(ctx: Ctx, block: jnp.ndarray) -> jnp.ndarray:
    """Fetch a stage's primary input for the elements at ``ctx.out_idx``.

    ``starts[0] is None`` means the block is already positionally aligned with
    ``out_idx`` (it is an in-register intermediate from a fused producer); otherwise
    gather at the block-local offsets.  Writing codec closures through this helper is
    what makes every Fully-Parallel stage *gather-capable*, i.e. evaluable at arbitrary
    indices -- the property fusion rule 2 (absorb into Group-Parallel values) relies on.
    """
    s = ctx.starts[0] if ctx.starts else 0
    if s is None:
        return block
    return block[ctx.out_idx - s]


def arg_at(ctx: Ctx, j: int, block: jnp.ndarray) -> jnp.ndarray:
    """``primary`` generalized to input position ``j``: fetch ``block`` at
    ``ctx.out_idx`` honouring its own start offset.  Operator stages
    (``_positional_inputs=True``) read *every* tiled/row input through this, so
    fusion can splice a producer into any position, not just position 0."""
    s = ctx.starts[j] if j < len(ctx.starts) else 0
    if s is None:
        return block
    return block[ctx.out_idx - s]


@dataclasses.dataclass
class FullyParallel(Stage):
    """out[i] = fn(ctx, *blocks);   inputs[k] tiled per specs[k]."""

    fn: Callable[..., jnp.ndarray]
    inputs: tuple[str, ...]
    specs: tuple[BufSpec, ...]
    out: str = "out"
    n_out: int = 0
    out_dtype: Any = jnp.int32
    elementwise: bool = True   # True iff fn reads inputs[0] only at position ctx.out_idx
    name: str = "fp"
    chunkability = CHUNK_ELEMENT   # out[i] independent => split anywhere

    def run_jnp(self, bufs: dict[str, jnp.ndarray]) -> jnp.ndarray:
        ctx = Ctx(out_idx=jnp.arange(self.n_out, dtype=jnp.int32),
                  starts=tuple(0 for _ in self.inputs))
        return self.fn(ctx, *[bufs[k] for k in self.inputs]).astype(self.out_dtype)


def group_ids(presum: jnp.ndarray, n_out: int, n_valid=None) -> jnp.ndarray:
    """Owning group of each output position ``i`` in ``[0, n_out)``:
    ``searchsorted(presum, i, side="right") - 1`` for a sorted ``presum`` with
    ``presum[0] == 0``, empty groups included.

    Every group after the first marks the position it starts at, and ``g[i]``
    counts the marks at or before ``i``: one scatter of ``n_groups`` marks and one
    prefix sum, where a binary search gathers ``presum`` once per level for every
    output.  Empty groups put several marks on one position.  Starts at or past
    ``n_valid`` (default ``n_out``) are dropped, so positions from ``n_valid`` on
    keep the last valid group.
    """
    starts = presum[1:]
    if n_valid is not None:
        starts = jnp.where(starts < n_valid, starts, n_out)
    marks = jnp.zeros((n_out,), jnp.int32).at[starts].add(
        1, mode="drop", indices_are_sorted=True)
    return jnp.cumsum(marks, dtype=jnp.int32)


@dataclasses.dataclass
class GroupParallel(Stage):
    """Balanced 1->N expansion (paper §4 'Scheduling Group-Parallel for Load Balance').

    out[i]:  g   = searchsorted(presum, i, side='right') - 1
             pos = i - presum[g]
             out[i] = map_fn(ctx, value_fn(g, value-blocks...), pos, g)

    ``presum`` is the inclusive-prefix-sum of group counts with a leading 0
    (len n_groups+1) -- the paper's "one-time data scan".  ``g`` is computed by
    ``group_ids``, a prefix sum of group-start marks that equals the
    ``searchsorted`` above exactly.  ``value_fn`` materializes the per-group payload;
    absorbing a preceding Fully-Parallel stage here is exactly the paper's
    Fig. 7(c) fusion of bit-packing into the RLE kernel.
    """

    presum: str
    value_inputs: tuple[str, ...]
    value_specs: tuple[BufSpec, ...]
    # value_fn(ctx, g_idx, *value_blocks) -> per-group payload for group ids g_idx
    value_fn: Callable[..., jnp.ndarray]
    # map_fn(ctx, gval, pos, g, *extra_blocks) -> output elements
    map_fn: Callable[..., jnp.ndarray]
    out: str = "out"
    n_out: int = 0
    out_dtype: Any = jnp.int32
    n_groups: int = 0
    extra_inputs: tuple[str, ...] = ()  # whole-buffer metadata (dictionaries, offsets)
    name: str = "gp"
    # per-group output offsets ([0, c_0, c_0+c_1, ...], len n_groups+1) computed by
    # the ENCODER on the host -- the run/chunk metadata group-boundary chunking
    # plans with (ir.group_chunk_layout).  Host-side planning data only: it is
    # identified like a lifted operand (dtype/shape, never value -- see
    # ir._meta_tokens host_meta handling), so it does not enter program identity,
    # and it never transfers (the device recomputes presum from counts).
    host_group_presum: Any = None
    chunkability = CHUNK_GROUP   # splits only where whole groups do

    def run_jnp(self, bufs: dict[str, jnp.ndarray]) -> jnp.ndarray:
        presum = bufs[self.presum]
        i = jnp.arange(self.n_out, dtype=jnp.int32)
        g = group_ids(presum, self.n_out)
        pos = i - presum[g]
        ctx = Ctx(out_idx=i, starts=tuple(0 for _ in self.value_inputs))
        gval = self.value_fn(ctx, g, *[bufs[k] for k in self.value_inputs])
        extras = [bufs[k] for k in self.extra_inputs]
        return self.map_fn(ctx, gval, pos, g, *extras).astype(self.out_dtype)


@dataclasses.dataclass
class NonParallel(Stage):
    """Chunked serial decode executed lane-lockstep (paper §4 'towards SIMT').

    Specialized to interleaved rANS (the paper's N.P. exemplar).  Buffers:
      streams: (max_words, n_chunks) uint16 striped words (chunk-transposed layout),
      states:  (n_chunks,) uint32 initial decoder states,
      tables:  (sym, freq, cum) alphabet tables, each (4096,) int32.
    Decodes n_chunks * chunk_size symbols; chunk c owns out[c*chunk_size:(c+1)*chunk_size].
    ``out_map`` post-maps decoded symbols (fusion target).
    """

    streams: str
    states: str
    sym_tab: str
    freq_tab: str
    cum_tab: str
    chunk_size: int
    n_chunks: int
    # out_map(ctx, syms) -> output elements; identity by default
    out_map: Callable[..., jnp.ndarray] | None = None
    out: str = "out"
    n_out: int = 0
    out_dtype: Any = jnp.uint8
    name: str = "np"
    # actual (pre-padding) compressed word count per chunk, host planning data
    # emitted by the encoder (per-group compressed-byte offsets = cumsum * 2);
    # identified by dtype/shape only, never transferred.  Recorded for the
    # unpadded-stripe follow-on (ROADMAP) -- today's planner prices the padded
    # stripe that actually transfers, so nothing reads it yet.
    host_group_words: Any = None
    # serial within a chunk, but chunks are independent: splits where whole
    # chunks (= groups) do.  The stripe layout interleaves chunks along axis 1,
    # so a group span is a column slice streams[:, g0:g1].
    chunkability = CHUNK_GROUP

    def run_jnp(self, bufs: dict[str, jnp.ndarray]) -> jnp.ndarray:
        from repro.algos.ans import decode_chunks_jnp  # avoids import cycle

        syms = decode_chunks_jnp(
            bufs[self.streams], bufs[self.states], bufs[self.sym_tab],
            bufs[self.freq_tab], bufs[self.cum_tab], self.chunk_size)
        flat = syms.reshape(-1)[: self.n_out]
        if self.out_map is not None:
            ctx = Ctx(out_idx=jnp.arange(self.n_out, dtype=jnp.int32))
            flat = self.out_map(ctx, flat)
        return flat.astype(self.out_dtype)


@dataclasses.dataclass
class Aux(Stage):
    """Whole-array auxiliary op (cumsum, scatter-patch).  Fusion barrier."""

    fn: Callable[..., jnp.ndarray]
    inputs: tuple[str, ...]
    out: str = "out"
    n_out: int = 0
    out_dtype: Any = jnp.int32
    name: str = "aux"
    chunkability = CHUNK_NONE   # whole-array op (cumsum, scatter) by definition

    def run_jnp(self, bufs: dict[str, jnp.ndarray]) -> jnp.ndarray:
        return self.fn(*[bufs[k] for k in self.inputs]).astype(self.out_dtype)


@dataclasses.dataclass
class Reduce(Stage):
    """Aggregate an item axis into a tiny partial vector (operator fusion).

    ``fn(ctx, *blocks) -> (n_out,)`` computes partial sums over the items at
    ``ctx.out_idx`` (predicated sums, segment-sums); because the reduction is
    additive, partials over any disjoint cover of ``[0, n_in)`` sum to the
    whole -- that is what makes a Reduce element-chunkable along its *item*
    axis even though ``n_out`` is a handful of accumulator lanes, not rows.
    Inputs are read positionally through ``arg_at`` (``_positional_inputs``),
    so fusion can graft whole decode chains into any input slot and the
    decompressed column never materializes at HBM.
    """

    fn: Callable[..., jnp.ndarray]
    inputs: tuple[str, ...]
    specs: tuple[BufSpec, ...]
    n_in: int = 0               # item-axis length (rows, or RLE runs)
    out: str = "agg"
    n_out: int = 0              # accumulator lanes (n_lanes * n_segments)
    out_dtype: Any = jnp.float32
    name: str = "reduce"
    chunkability = CHUNK_ELEMENT    # partials over any item cover sum to whole
    _positional_inputs = True

    def run_jnp(self, bufs: dict[str, jnp.ndarray]) -> jnp.ndarray:
        ctx = Ctx(out_idx=jnp.arange(self.n_in, dtype=jnp.int32),
                  starts=tuple(0 for _ in self.inputs))
        return self.fn(ctx, *[bufs[k] for k in self.inputs]).astype(self.out_dtype)


# --------------------------------------------------------------------------- helpers
def compose_fp(first: FullyParallel, second: FullyParallel) -> FullyParallel:
    """Fuse two Fully-Parallel stages: second(first(x)).  Requires the second stage to
    be elementwise in its primary input (out[i] reads first_out[i])."""
    assert second.elementwise, "cannot compose into a non-elementwise consumer"
    assert second.inputs[0] == first.out
    f_fn, s_fn = first.fn, second.fn
    n_first = len(first.inputs)

    def fused(ctx: Ctx, *blocks):
        f_ctx = Ctx(out_idx=ctx.out_idx, starts=ctx.starts[:n_first])
        mid = f_fn(f_ctx, *blocks[:n_first]).astype(first.out_dtype)
        # None start: `mid` is an in-register intermediate positionally aligned with
        # out_idx -- the consumer must not gather it by global index
        s_ctx = Ctx(out_idx=ctx.out_idx, starts=(None,) + ctx.starts[n_first:])
        return s_fn(s_ctx, mid, *blocks[n_first:])

    return FullyParallel(
        fn=fused,
        inputs=first.inputs + second.inputs[1:],
        specs=first.specs + second.specs[1:],
        out=second.out, n_out=second.n_out, out_dtype=second.out_dtype,
        elementwise=first.elementwise,
        name=f"{first.name}+{second.name}")


def compose_positional(first: FullyParallel, cons: Stage, j: int) -> Stage:
    """Fuse a Fully-Parallel producer into input position ``j`` of a consumer
    whose closure reads every input through ``arg_at`` (``_positional_inputs``:
    operator predicate/projection stages and ``Reduce``).  The producer's
    gather-capable closure evaluates at the consumer's indices; its result is
    handed over in-register with a ``None`` start (positionally aligned)."""
    n_first = len(first.inputs)
    f_fn, c_fn = first.fn, cons.fn

    def fused(ctx: Ctx, *blocks):
        f_ctx = Ctx(out_idx=ctx.out_idx, starts=ctx.starts[j:j + n_first])
        mid = f_fn(f_ctx, *blocks[j:j + n_first]).astype(first.out_dtype)
        s_starts = ctx.starts[:j] + (None,) + ctx.starts[j + n_first:]
        return c_fn(Ctx(out_idx=ctx.out_idx, starts=s_starts),
                    *blocks[:j], mid, *blocks[j + n_first:])

    new = dataclasses.replace(
        cons, fn=fused,
        inputs=cons.inputs[:j] + first.inputs + cons.inputs[j + 1:],
        specs=cons.specs[:j] + first.specs + cons.specs[j + 1:],
        name=f"{first.name}>{cons.name}")
    new._positional_inputs = True  # type: ignore[attr-defined]
    return new


def identity_value_fn(ctx: Ctx, g: jnp.ndarray, values: jnp.ndarray) -> jnp.ndarray:
    start = ctx.starts[0] if ctx.starts else 0
    return values[g - start] if not isinstance(start, int) or start != 0 else values[g]


def run_stages_jnp(stages: Sequence[Stage], bufs: dict[str, jnp.ndarray]) -> jnp.ndarray:
    """Reference executor: run every stage with the pure-jnp backend."""
    bufs = dict(bufs)
    out = None
    for st in stages:
        out = st.run_jnp(bufs)
        bufs[st.out] = out
    return out
