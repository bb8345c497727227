"""Group-Parallel Pallas TPU kernel (paper §4, Fig. 10).

The paper balances skewed group sizes by letting multiple GPU blocks co-process one
group and one block span many groups.  The TPU-native equivalent implemented here is
*output-centric balanced decomposition*: every grid step produces a fixed (L*S, C)
output tile -- equal work regardless of the group-size distribution -- and locates each
element's owning group with an in-VMEM branchless binary search over the presum.

Data-dependent blocking: a tile starting at output offset o touches groups starting at
``fg = searchsorted(presum, o, 'right') - 1``.  ``fg`` per tile is precomputed with one
cheap scan (the paper's one-time data scan) and fed through *scalar prefetch*, so the
BlockSpec index maps DMA exactly the presum/value window each tile needs
(element-indexed ``pl.Element`` windows).  A tile of T outputs
intersects at most T+1 groups (counts are >= 1), bounding the window statically.

Value inputs whose tile ratio is a runtime meta operand (bitpack's ``bit_width``
after fusion rule 2) cannot drive a static DMA window, so they stay whole-resident
in VMEM instead of windowed.

Absorbed Fully-Parallel producers (fusion rule 2) run on the gathered group values
inside this same kernel -- e.g. bit-packed RLE values never materialize, the paper's
Fig. 7(c).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.geometry import Geometry
from repro.core.patterns import Ctx, GroupParallel
from repro.kernels.fully_parallel import _out_index_grid


def _upper_bound(presum_blk: jnp.ndarray, q: jnp.ndarray, length: int) -> jnp.ndarray:
    """Branchless binary search: first index j with presum_blk[j] > q."""
    lo = jnp.zeros_like(q)
    hi = jnp.full_like(q, length)
    for _ in range(max(1, math.ceil(math.log2(length + 1)))):
        mid = (lo + hi) >> 1
        go_right = presum_blk[mid] <= q
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    return lo


def group_parallel_call(stage: GroupParallel, bufs: dict[str, jnp.ndarray],
                        geom: Geometry, interpret: bool = False,
                        group_cap: int | None = None) -> jnp.ndarray:
    n = stage.n_out
    rows, cols = geom.L * geom.S, geom.C
    tile = rows * cols
    n_tiles = max(1, math.ceil(n / tile))
    # max groups a tile can intersect; a host-derived hint may tighten this
    gcap = min(stage.n_groups, tile + 1) if group_cap is None \
        else min(group_cap, stage.n_groups)
    gcap = max(gcap, 1)

    presum = bufs[stage.presum].astype(jnp.int32)
    # pad so Element-windows never run off the end; sentinel keeps the search valid
    presum_p = jnp.concatenate(
        [presum, jnp.full((gcap + 2,), jnp.int32(2**31 - 1))])
    # one-time scan: first group per tile (scalar prefetch)
    tile_starts = jnp.arange(n_tiles, dtype=jnp.int32) * tile
    fg = (jnp.searchsorted(presum, tile_starts, side="right") - 1).astype(jnp.int32)
    fg = jnp.maximum(fg, 0)

    value_arrays = []
    value_specs = []
    value_units: list[tuple[int, int]] = []  # (num, den) per value input
    for spec, name in zip(stage.value_specs, stage.value_inputs):
        arr = bufs[name]
        if spec.kind == "full" or spec.num_op:
            # whole-resident: small metadata, or a tile whose ratio is a runtime
            # operand (no static window size exists for it)
            value_specs.append(pl.BlockSpec(arr.shape,
                                            lambda i, s, _nd=arr.ndim: (0,) * _nd))
            value_units.append((0, 1))  # start derived as None
            value_arrays.append(arr)
            continue
        num, den = spec.num, spec.den
        blen = (gcap * num) // den + (2 if den > 1 else 1)
        pad = jnp.zeros((blen + 2,), arr.dtype)
        value_arrays.append(jnp.concatenate([arr.reshape(-1), pad]))
        value_specs.append(pl.BlockSpec(
            (pl.Element(blen),),
            lambda i, s, _n=num, _d=den: ((s[i] * _n) // _d,)))
        value_units.append((num, den))
    extra_arrays = [bufs[k] for k in stage.extra_inputs]
    extra_specs = [pl.BlockSpec(a.shape, lambda i, s, _nd=a.ndim: (0,) * _nd)
                   for a in extra_arrays]

    def kernel(sref, presum_ref, *refs):
        value_refs = refs[: len(value_arrays)]
        extra_refs = refs[len(value_arrays):-1]
        o_ref = refs[-1]
        i = pl.program_id(0)
        fg_i = sref[i]
        out_idx = _out_index_grid(i, rows, cols)
        pblk = presum_ref[...]
        g_local = _upper_bound(pblk, jnp.minimum(out_idx, n - 1), gcap + 1) - 1
        g_local = jnp.clip(g_local, 0, gcap)
        g = g_local + fg_i
        pos = jnp.minimum(out_idx, n - 1) - pblk[g_local]
        starts = tuple(None if (nu, de) == (0, 1) else (fg_i * nu) // de
                       for nu, de in value_units)
        ctx = Ctx(out_idx=out_idx, starts=starts)
        gval = stage.value_fn(Ctx(out_idx=g, starts=starts), g,
                              *[r[...] for r in value_refs])
        vals = stage.map_fn(ctx, gval, pos, g, *[r[...] for r in extra_refs])
        o_ref[...] = jnp.where(out_idx < n, vals, 0).astype(o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((pl.Element(gcap + 2),), lambda i, s: (s[i],))]
        + value_specs + extra_specs,
        out_specs=pl.BlockSpec((rows, cols), lambda i, s: (i, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles * rows, cols), stage.out_dtype),
        interpret=interpret,
    )(fg, presum_p, *value_arrays, *extra_arrays)
    return out.reshape(-1)[:n]
