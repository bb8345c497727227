"""Training driver: ``python -m repro.launch.train --arch <id> [--smoke] ...``

Wires together the full stack: compressed data loader (the paper's pipeline feeding
the step), train_step (FSDP+TP via shardings when a mesh is available), AdamW,
fault-tolerant loop with compressed checkpoints.  On this CPU container use --smoke
(reduced configs); on a real TPU slice the same driver runs the production configs
with ``make_production_mesh`` and ``TPU_PERF_FLAGS``.
"""
from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SMOKES
from repro.data.loader import CompressedTokenLoader
from repro.launch.mesh import TPU_PERF_FLAGS, make_production_mesh, shard_tree
from repro.models import get_model
from repro.models.sharding_ctx import set_mesh_context
from repro.train import checkpoint as ckpt_mod
from repro.train.loop import LoopConfig, run
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import make_train_step
from repro.train import optimizer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the (16,16) mesh (requires a real slice)")
    args = ap.parse_args()

    cfg = (SMOKES if args.smoke else ARCHS)[args.arch]
    model = get_model(cfg)
    if args.production_mesh:
        # append: flags already in the variable stay, and so do ours
        os.environ["LIBTPU_INIT_ARGS"] = " ".join(
            filter(None, (os.environ.get("LIBTPU_INIT_ARGS"), TPU_PERF_FLAGS)))
        mesh = make_production_mesh()
        set_mesh_context(mesh)
    params, specs = model.init(jax.random.PRNGKey(0))
    opt_state = optimizer.init(params)
    if args.production_mesh:
        shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                              params)
        shardings = shard_tree(shapes, specs, mesh)
        params = jax.tree.map(jax.device_put, params, shardings)

    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps)
    step = jax.jit(make_train_step(cfg, opt_cfg, remat=args.remat,
                                   microbatch=args.microbatch),
                   donate_argnums=(0, 1))
    # the ZipFlow-compressed token pipeline: fixed-width packed transfer + fused
    # on-device decode prologue
    loader = CompressedTokenLoader(cfg.vocab, args.batch, args.seq)
    decode = loader.decode_fn()

    def step_with_decode(p, o, bufs):
        return step(p, o, decode(bufs))

    def batch_fn(i):
        return {k: jax.device_put(v) for k, v in loader.encode_host(i).items()}

    loop_cfg = LoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                          ckpt_every=args.ckpt_every)
    params, opt_state, hist = run(loop_cfg, step_with_decode, params, opt_state,
                                  batch_fn)
    print(f"[train] done: final loss {hist[-1]['loss']:.4f}; "
          f"data moved compressed at ratio {loader.ratio:.2f}x; "
          f"checkpoints in {args.ckpt_dir} "
          f"(ratio {ckpt_mod.compression_report(args.ckpt_dir)['ratio']:.3f})")


if __name__ == "__main__":
    main()
