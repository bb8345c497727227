"""ZipFlow-JAX quickstart: compress a column, move it, decompress on device.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import plan as P
from repro.core.compiler import compile_decoder, device_buffers
from repro.core.fusion import fuse
from repro.core.plan import lower

enable_compile_cache()

# 1. some TPC-H-shaped data: dates with ~2.5k distinct values
rng = np.random.default_rng(0)
column = rng.integers(8035, 10591, 1_000_000).astype(np.int32)

# 2. a nested plan from the paper's Table 2: dictionary | bit-packing
plan = P.Plan("dictionary", children={"index": P.make_plan("bitpack")})

# 3. compress on the host
enc = P.encode(plan, column)
print(f"plan {plan.describe()}: {enc.plain_nbytes / 1e6:.1f} MB -> "
      f"{enc.compressed_nbytes / 1e6:.2f} MB (ratio {enc.ratio:.1f}x)")

# 4. the compiler lowers the plan to pattern stages and fuses them
stages = lower(enc)
fused = fuse(list(stages))
print(f"stages: {[s.name for s in stages]} -> fused: {[s.name for s in fused]}")

# 4b. ...or to a DecodeGraph: buffer defs + the structural signature that keys the
#     ProgramCache (blobs with equal signatures share ONE jitted program)
graph = P.lower_graph(enc)
print(f"graph: {graph.nesting}, {len(graph.buffers)} transfer buffers, "
      f"signature {graph.signature[:12]}")

# 5. move the compressed buffers and decode on device (pure-jnp backend here;
#    backend='pallas' runs the Pallas kernels and needs interpret= passed
#    explicitly -- the TPU compiler refuses them today, so interpret=True)
decoder = compile_decoder(enc, backend="jnp", fuse=True)
out = decoder(device_buffers(enc))
assert np.array_equal(np.asarray(out), column)
print("device decode matches:", True)

# 6. device-geometry scheduling: the <L,S,C> native config for this chip
from repro.core.geometry import native_config, chip
g = native_config("fp", chip("v5e"))
print(f"v5e Fully-Parallel native config: {g} (tile={g.tile} elems)")
