"""Device-geometry scheduling: the paper's <L, S, C> configuration vector, re-derived
for the TPU execution model (paper §4).

On a GPU, <L, S, C> = (main-loop iterations, threads per block, contiguous elements per
thread): the tile processed by one block is L*S*C elements.  TPUs have no independent
threads; the unit of scheduling is the VMEM block fetched per grid step of a
``pallas_call``.  We therefore map:

    L  -> iterations of the in-kernel loop over (S, C) sub-tiles (amortizes grid/DMA
          overhead exactly like the paper's thread main loop),
    S  -> sublane extent of the sub-tile (multiples of 8, the VPU sublane count),
    C  -> lane extent of the sub-tile (multiples of 128, the VPU lane count).

One grid step owns an (L*S, C) VMEM block; grid = ceil(N / (L*S*C)).  The product
L*S*C is the paper's "tile size".  Choosing <L,S,C> trades VMEM footprint, DMA
double-buffering efficiency and grid overhead -- the same trade the paper tunes per-GPU,
here tuned per TPU generation.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Iterable


@dataclasses.dataclass(frozen=True)
class Geometry:
    """The paper's <L, S, C> kernel configuration vector (TPU interpretation)."""

    L: int  # in-kernel loop iterations (grid-overhead amortization)
    S: int  # sublane extent of one sub-tile (multiple of 8)
    C: int  # lane extent of one sub-tile (multiple of 128)

    @property
    def tile(self) -> int:
        """Elements processed per grid step (the paper's L*S*C tile size)."""
        return self.L * self.S * self.C

    @property
    def block_shape(self) -> tuple[int, int]:
        """VMEM block shape for one grid step."""
        return (self.L * self.S, self.C)

    def vmem_bytes(self, itemsize: int, n_buffers: int = 2) -> int:
        """Approximate VMEM footprint (double-buffered in+out by default)."""
        return self.tile * itemsize * n_buffers * 2  # x2: pallas double-buffers DMA

    def grid(self, n: int) -> int:
        return max(1, math.ceil(n / self.tile))

    def __str__(self) -> str:  # <L,S,C> like the paper
        return f"<{self.L},{self.S},{self.C}>"


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-chip resource table (the paper's per-GPU architectural features, §4/§5.5).

    TPU generations differ in VMEM capacity, HBM bandwidth, MXU throughput and
    grid-step overhead the same way MI50/A100/H100/MI300X differ in SM count, cache and
    wavefront size; this table is what makes a config "Native" to a chip.
    """

    name: str
    vmem_bytes: int            # per-core VMEM usable by one kernel
    sublanes: int              # VPU second-minor dim (8 on all current TPUs)
    lanes: int                 # VPU minor dim (128 on all current TPUs)
    hbm_gbps: float            # HBM bandwidth, GB/s
    peak_bf16_tflops: float    # MXU peak, TFLOP/s
    ici_gbps_per_link: float   # inter-chip link bandwidth, GB/s
    grid_step_overhead_ns: float  # per-grid-step scheduling + DMA setup cost
    vpu_elems_per_ns: float    # VPU elementwise throughput (elements/ns, 32-bit)
    host_link_gbps: float      # host<->device (PCIe) bandwidth, GB/s


# Resource tables for the chips this framework targets.  v5e numbers match the roofline
# constants mandated for this exercise; others are public-datasheet-scale figures used
# only for *relative* native-vs-shared config studies (paper Fig. 22 analogue).
CHIPS: dict[str, ChipSpec] = {
    "v4": ChipSpec("v4", vmem_bytes=16 * 2**20, sublanes=8, lanes=128,
                   hbm_gbps=1228.0, peak_bf16_tflops=275.0, ici_gbps_per_link=50.0,
                   grid_step_overhead_ns=250.0, vpu_elems_per_ns=2.4,
                   host_link_gbps=16.0),
    "v5e": ChipSpec("v5e", vmem_bytes=16 * 2**20, sublanes=8, lanes=128,
                    hbm_gbps=819.0, peak_bf16_tflops=197.0, ici_gbps_per_link=50.0,
                    grid_step_overhead_ns=200.0, vpu_elems_per_ns=1.9,
                    host_link_gbps=32.0),
    "v5p": ChipSpec("v5p", vmem_bytes=32 * 2**20, sublanes=8, lanes=128,
                    hbm_gbps=2765.0, peak_bf16_tflops=459.0, ici_gbps_per_link=100.0,
                    grid_step_overhead_ns=180.0, vpu_elems_per_ns=3.7,
                    host_link_gbps=32.0),
    "v6e": ChipSpec("v6e", vmem_bytes=32 * 2**20, sublanes=8, lanes=128,
                    hbm_gbps=1640.0, peak_bf16_tflops=918.0, ici_gbps_per_link=100.0,
                    grid_step_overhead_ns=150.0, vpu_elems_per_ns=3.9,
                    host_link_gbps=64.0),
}

# ``jax.Device.device_kind`` of each TPU generation in the table, as JAX reports
# it (checked against described ``v4:2x2x1``/``v5p:2x2x1``/``v5e:2x2``/``v6e:2x2``
# topologies).  A TPU whose kind is missing here is an error, never planned
# with another chip's numbers.
DEVICE_KINDS: dict[str, str] = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5": "v5p",
    "TPU v6 lite": "v6e",
}

# Off a TPU (CPU tests and rehearsals) there is no chip to read, so plans model
# the chip this repository targets.
MODEL_CHIP = "v5e"


def resolve_chip(name: str | None = None) -> str:
    """CHIPS key to plan for: ``name`` when given, else the attached TPU's
    ``device_kind``, else ``MODEL_CHIP`` when JAX's devices are not TPUs."""
    if name is not None:
        if name not in CHIPS:
            raise ValueError(f"unknown chip {name!r} (known: {sorted(CHIPS)})")
        return name
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return MODEL_CHIP
    try:
        return DEVICE_KINDS[dev.device_kind]
    except KeyError:
        raise ValueError(f"no chip table entry for device_kind "
                         f"{dev.device_kind!r} (known: "
                         f"{sorted(DEVICE_KINDS)})") from None


def chip(name: str | None = None) -> ChipSpec:
    return CHIPS[resolve_chip(name)]


# ----------------------------------------------------------------------------- spaces
# Config spaces per pattern, powers of two only (paper Table 3).  The GPU table's
# warp-size lower bound on S becomes the sublane count; C's dtype coupling on GPU
# (4/dtype.size vectorization) becomes the lane multiple.

def fp_space(spec: ChipSpec, itemsize: int = 4) -> Iterable[Geometry]:
    """Fully-Parallel space: L in 2^0..2^4, S in {8..512}, C in {128..1024}."""
    for L in (1, 2, 4, 8, 16):
        for S in (8, 16, 32, 64, 128, 256, 512):
            for C in (128, 256, 512, 1024):
                g = Geometry(L, S, C)
                if g.vmem_bytes(itemsize) <= spec.vmem_bytes:
                    yield g


def gp_space(spec: ChipSpec, itemsize: int = 4) -> Iterable[Geometry]:
    """Group-Parallel space: output-centric tiles; L fixed small (the balanced
    decomposition makes group sizes irrelevant), S and C sized to VMEM."""
    for L in (1, 2, 4):
        for S in (8, 16, 32, 64, 128, 256, 512):
            for C in (128, 256, 512, 1024):
                g = Geometry(L, S, C)
                # expand kernels hold presum + values + out: 3 buffers
                if g.vmem_bytes(itemsize, n_buffers=3) <= spec.vmem_bytes:
                    yield g


def np_space(spec: ChipSpec, itemsize: int = 4) -> Iterable[Geometry]:
    """Non-Parallel space: S fixed to sublanes (the 'warp size' analogue), C = chunks
    per lane group, L = grid steps worth of chunk batches."""
    for L in (1, 2, 4, 8):
        for C in (128, 256, 512, 1024):
            g = Geometry(L, spec.sublanes, C)
            if g.vmem_bytes(itemsize, n_buffers=4) <= spec.vmem_bytes:
                yield g


SPACES: dict[str, Callable[..., Iterable[Geometry]]] = {
    "fp": fp_space,
    "gp": gp_space,
    "np": np_space,
}


# ------------------------------------------------------------------------- cost model
def analytic_cost_ns(pattern: str, geom: Geometry, n_elems: int, itemsize: int,
                     spec: ChipSpec, bytes_in: int | None = None,
                     bytes_out: int | None = None) -> float:
    """Analytic per-kernel cost model used for offline geometry tuning.

    Three terms, mirroring how the paper reasons about its config space:
      * HBM traffic time   (compulsory: bytes in + bytes out at hbm_gbps)
      * grid overhead      (grid steps x per-step cost; shrinks with larger L*S*C)
      * VPU time           (elementwise work; grows with poorly shaped tiles)
    The model is intentionally monotone in each of L, S, C until the VMEM cliff --
    the structure the paper's pruned search exploits (Table 3).
    """
    bytes_out = n_elems * itemsize if bytes_out is None else bytes_out
    bytes_in = bytes_out if bytes_in is None else bytes_in
    hbm_ns = (bytes_in + bytes_out) / spec.hbm_gbps  # GB/s == bytes/ns
    steps = geom.grid(n_elems)
    overhead_ns = steps * spec.grid_step_overhead_ns
    # VPU term: vector issue is per (sublanes x lanes) register; narrow C wastes lanes,
    # narrow S wastes sublanes.
    lane_eff = min(1.0, geom.C / spec.lanes) if geom.C < spec.lanes else 1.0
    sub_eff = min(1.0, geom.S / spec.sublanes)
    work_ns = n_elems / (spec.vpu_elems_per_ns * lane_eff * sub_eff)
    if pattern == "gp":
        work_ns *= 1.35   # binary search over presum adds VPU ops per element
    if pattern == "np":
        work_ns *= 4.0    # serial decode: table lookups + renorm selects per symbol
        # N.P. parallelism is bounded by chunks in flight = S*C per step
        chunk_par = geom.S * geom.C
        work_ns = max(work_ns, n_elems / max(1, chunk_par) * 2.0)
    # VMEM pressure cliff: double-buffering dies when the working set (same buffer
    # count the per-pattern config spaces use) no longer fits.
    n_buffers = {"fp": 2, "gp": 3, "np": 4}[pattern]
    if geom.vmem_bytes(itemsize, n_buffers=n_buffers) > spec.vmem_bytes:
        hbm_ns *= 4.0
    return hbm_ns + overhead_ns + work_ns


def native_config(pattern: str, spec: ChipSpec, n_elems: int = 1 << 24,
                  itemsize: int = 4) -> Geometry:
    """Best geometry under the analytic model -- a chip's 'Native Config' (§5.5)."""
    space = list(SPACES[pattern](spec, itemsize))
    return min(space, key=lambda g: analytic_cost_ns(pattern, g, n_elems, itemsize, spec))


@functools.lru_cache(maxsize=None)
def native_subtile(pattern: str, chip_name: str, itemsize: int = 4) -> int:
    """S*C of the chip's native config: the elements one grid-step sub-tile
    covers (one L-loop iteration).  The planner's chunk-size ladder snaps
    element-chunk boundaries to multiples of this, so every streamed decode
    launch covers whole kernel tiles of the pattern it runs."""
    pat = pattern if pattern in SPACES else "fp"
    g = native_config(pat, CHIPS[chip_name], itemsize=itemsize)
    return int(g.S) * int(g.C)
