import os
if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512")))
# The lines above MUST precede every other import: jax locks the device count at
# first initialization, and the production meshes below need 512 host placeholders.
# Only a run as a script sets the flag; importing this module changes nothing.

"""Multi-pod dry-run: prove the distribution config is coherent without hardware.

For every (architecture x input shape) cell and both production meshes, lower the
appropriate step (train_step / prefill / serve decode_step) with ShapeDtypeStruct
inputs, ``.compile()`` it, and record:
  * memory_analysis()   -- proves the program fits per-device HBM,
  * cost_analysis()     -- per-chip FLOPs / bytes for the roofline,
  * collective wire bytes parsed from the compiled HLO.

Usage:
  python -m repro.launch.dryrun --arch dbrx-132b --shape train_4k --mesh multipod
  python -m repro.launch.dryrun --all --mesh pod --out experiments/dryrun
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCHS, SHAPES
from repro.launch.mesh import make_production_mesh, shard_tree
from repro.models import cell_status, get_model
from repro.roofline import analysis, hlo_cost
from repro.train import optimizer
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import make_train_step

# per-arch dry-run training knobs (remat policy, microbatch) -- revisited in §Perf
TRAIN_KNOBS: dict[str, dict] = {
    "nemotron-4-15b": {"microbatch": 8, "remat": "full"},
    "dbrx-132b": {"microbatch": 16, "remat": "full"},
    "phi3.5-moe-42b-a6.6b": {"microbatch": 4, "remat": "full"},
    "phi3-mini-3.8b": {"microbatch": 4, "remat": "full"},
    "zamba2-7b": {"microbatch": 4, "remat": "full"},
    "rwkv6-7b": {"microbatch": 4, "remat": "full"},
    "qwen2-vl-2b": {"microbatch": 2, "remat": "full"},
    "seamless-m4t-medium": {"microbatch": 2, "remat": "full"},
    "qwen1.5-0.5b": {"microbatch": 1, "remat": "full"},
    "smollm-360m": {"microbatch": 4, "remat": "full"},
}


def abstract_init(model, key=None):
    """(param ShapeDtypeStructs, logical specs) without allocating anything."""
    captured = {}

    def initp(k):
        p, s = model.init(k)
        captured["specs"] = s
        return p

    shapes = jax.eval_shape(initp, jax.random.PRNGKey(0))
    return shapes, captured["specs"]


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             knobs: dict | None = None) -> dict:
    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    status = cell_status(cfg, shape)
    if status != "run":
        rec["status"] = status
        return rec
    t0 = time.time()
    model = get_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    from repro.models.sharding_ctx import set_mesh_context
    set_mesh_context(mesh)  # activation with_sharding_constraints inside the models
    chips = int(np.prod(list(mesh.shape.values())))
    p_shapes, p_logical = abstract_init(model)
    p_sh = shard_tree(p_shapes, p_logical, mesh)
    in_shapes, in_logical = model.input_specs(shape)
    in_sh = shard_tree(in_shapes, in_logical, mesh)

    if shape.kind == "train":
        kn = dict(TRAIN_KNOBS.get(arch, {}))
        kn.update(knobs or {})
        # per-microbatch batch must stay divisible by the fsdp axes or XLA
        # replicates the activations (measured: dbrx multipod mb16 -> 95 GB/dev)
        fsdp_size = int(np.prod([s for n, s in mesh.shape.items()
                                 if n != "model"]))
        mb = kn.get("microbatch", 1)
        while mb > 1 and (shape.global_batch // mb) % fsdp_size:
            mb //= 2
        kn["microbatch"] = mb
        step = make_train_step(cfg, AdamWConfig(),
                               remat=kn.get("remat", "full"),
                               microbatch=kn.get("microbatch", 1))
        o_shapes = jax.eval_shape(optimizer.init, p_shapes)
        o_logical = {"mu": p_logical, "nu": p_logical, "step": None}
        o_sh = shard_tree(o_shapes, o_logical, mesh)
        jitted = jax.jit(step, in_shardings=(p_sh, o_sh, in_sh),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(p_shapes, o_shapes, in_shapes)
        rec["knobs"] = kn
    elif shape.kind == "prefill":
        st_shapes = jax.eval_shape(
            lambda: model.make_state(shape.global_batch, shape.seq_len))
        st_sh = shard_tree(st_shapes, model.state_specs(shape.global_batch), mesh)
        fn = lambda p, b, st: model.prefill(p, b, st)
        jitted = jax.jit(fn, in_shardings=(p_sh, in_sh, st_sh),
                         donate_argnums=(2,))
        lowered = jitted.lower(p_shapes, in_shapes, st_shapes)
    else:  # decode
        st_shapes = jax.eval_shape(
            lambda: model.make_state(shape.global_batch, shape.seq_len))
        st_sh = shard_tree(st_shapes, model.state_specs(shape.global_batch), mesh)
        fn = lambda p, t, st: model.decode_step(p, t, st)
        jitted = jax.jit(fn, in_shardings=(p_sh, in_sh["token"], st_sh),
                         donate_argnums=(2,))
        lowered = jitted.lower(p_shapes, in_shapes["token"], st_shapes)

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # trip-count-aware walk (XLA's cost_analysis counts while bodies once)
    walk = hlo_cost.analyze(hlo)
    per_dev = int(mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    roof = analysis.Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        hlo_flops_per_chip=float(walk["flops"]),
        hlo_bytes_per_chip=float(walk["bytes"]),
        coll_bytes_per_chip=float(walk["coll_bytes"]),
        coll_breakdown=walk["collectives"],
        model_flops_total=analysis.model_flops(cfg, shape, shape.kind),
        per_device_bytes=per_dev,
        useful_bytes_per_chip=float(mem.argument_size_in_bytes
                                    + mem.output_size_in_bytes),
    )
    rec.update(status="ok", lower_s=round(t_lower, 1),
               compile_s=round(t_compile, 1),
               memory={"argument": int(mem.argument_size_in_bytes),
                       "output": int(mem.output_size_in_bytes),
                       "temp": int(mem.temp_size_in_bytes),
                       "alias": int(mem.alias_size_in_bytes),
                       "per_device_live": per_dev,
                       "fits_16g_hbm": bool(per_dev < 16 * 2**30)},
               roofline=roof.to_dict(),
               xla_raw_cost={"flops": float(ca.get("flops", 0.0)),
                             "bytes": float(ca.get("bytes accessed", 0.0))},
               hlo_ops={"n_instructions": hlo.count("=")})
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--microbatch", type=int, default=None)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    cells = []
    archs = sorted(ARCHS) if (args.all or args.arch is None) else [args.arch]
    shapes = sorted(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if args.mesh == "both" else [args.mesh == "multipod"]
    knobs = {}
    if args.remat:
        knobs["remat"] = args.remat
    if args.microbatch:
        knobs["microbatch"] = args.microbatch
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'mp' if mp else 'sp'}"
                out_path = os.path.join(args.out, tag + ".json")
                if os.path.exists(out_path):
                    print(f"[dryrun] {tag}: cached")
                    continue
                print(f"[dryrun] {tag}: lowering...", flush=True)
                try:
                    rec = run_cell(arch, shape, mp, knobs or None)
                except Exception as e:  # noqa: BLE001 -- record the failure
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "mp" if mp else "sp",
                           "status": f"FAIL: {type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                json.dump(rec, open(out_path, "w"), indent=1)
                status = rec.get("status", "?")
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" bottleneck={r['bottleneck']}"
                             f" mem/dev={rec['memory']['per_device_live'] / 2**30:.2f}G"
                             f" compile={rec['compile_s']}s")
                print(f"[dryrun] {tag}: {status[:100]}{extra}", flush=True)


if __name__ == "__main__":
    main()
