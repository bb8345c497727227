"""End-to-end LM training through the compressed data pipeline.

Trains a reduced qwen1.5-family model for a few hundred steps on CPU; tokens move
host->device bit-packed (fixed width) and decompress inside the jitted step prologue.
Demonstrates: ZipFlow loader, AdamW, fault-tolerant loop with compressed
checkpoints, restart-from-checkpoint.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 200]
(The same driver scales to the full config on a TPU slice via
 ``python -m repro.launch.train --arch qwen1.5-0.5b --production-mesh``.)
"""
import argparse
import dataclasses
import tempfile

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import SMOKES
from repro.data.loader import CompressedTokenLoader
from repro.models import get_model
from repro.train import checkpoint as ckpt
from repro.train import optimizer
from repro.train.loop import LoopConfig, run
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import make_train_step

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--batch", type=int, default=8)
ap.add_argument("--seq", type=int, default=128)
ap.add_argument("--d-model", type=int, default=128)
ap.add_argument("--layers", type=int, default=4)
args = ap.parse_args()
enable_compile_cache()

cfg = dataclasses.replace(
    SMOKES["qwen1.5-0.5b"], d_model=args.d_model, n_layers=args.layers,
    n_heads=4, n_kv_heads=4, d_ff=args.d_model * 3, vocab=4096)
model = get_model(cfg)
params, _ = model.init(jax.random.PRNGKey(0))
n = sum(int(x.size) for x in jax.tree.leaves(params))
print(f"model: {cfg.name} variant, {n / 1e6:.2f}M params")

opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps)
opt_state = optimizer.init(params)
step = jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=(0, 1))

loader = CompressedTokenLoader(cfg.vocab, args.batch, args.seq)
decode = loader.decode_fn()


def step_with_decode(p, o, bufs):
    # ZipFlow integration: decompression is the first op of the jitted step
    return step(p, o, decode(bufs))


def batch_fn(i):
    return {k: jax.device_put(v) for k, v in loader.encode_host(i).items()}


with tempfile.TemporaryDirectory() as d:
    loop_cfg = LoopConfig(total_steps=args.steps, ckpt_dir=d,
                          ckpt_every=max(args.steps // 4, 10), log_every=20)
    params, opt_state, hist = run(loop_cfg, step_with_decode, params,
                                  opt_state, batch_fn)
    print(f"\nloss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f} over "
          f"{len(hist)} steps")
    print(f"tokens moved compressed: ratio {loader.ratio:.2f}x "
          f"({loader.bytes_compressed / 1e6:.1f} MB vs "
          f"{loader.bytes_plain / 1e6:.1f} MB plain)")
    rep = ckpt.compression_report(d)
    print(f"checkpoint shards: ratio {rep['ratio']:.3f}x")
assert hist[-1]["loss"] < hist[0]["loss"], "training did not learn"
print("OK")
