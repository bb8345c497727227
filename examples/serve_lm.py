"""Batched serving with continuous batching + compressed KV paging.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import SMOKES
from repro.models import get_model
from repro.serve.engine import Request, ServeEngine
from repro.serve.kvcache import page_in, page_out

enable_compile_cache()
cfg = SMOKES["qwen1.5-0.5b"]
model = get_model(cfg)
params, _ = model.init(jax.random.PRNGKey(0))

# --- continuous batching over 2 slots, 5 requests ---
eng = ServeEngine(cfg, params, batch_slots=2, max_len=128, eos=-1)
rng = np.random.default_rng(0)
for rid in range(5):
    eng.submit(Request(rid, rng.integers(0, cfg.vocab, 6).astype(np.int32),
                       max_new=8))
done = eng.run_to_completion(max_steps=500)
for rid in sorted(done):
    print(f"request {rid}: generated {done[rid]}")

# --- ZipFlow KV paging: quantize+bitpack a cold cache block to host ---
block = jnp.asarray(rng.normal(size=(2, 64, cfg.n_kv_heads, cfg.hd))
                    .astype(np.float32))
pb = page_out(block)
restored = page_in(pb, jnp.float32)
err = float(jnp.max(jnp.abs(restored - block)))
print(f"\nKV paging: {block.nbytes} B block -> {pb.packed.nbytes} B on the wire "
      f"({block.nbytes / pb.packed.nbytes:.1f}x), max dequant err {err:.4f}")
