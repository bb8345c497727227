"""End-to-end TPC-H data movement + query execution (the paper's headline scenario).

Compresses the columns of TPC-H Q1/Q6 with the paper's Table-2 plans, moves them
host->device with Johnson-ordered pipelining, decompresses, and runs the queries in
the JAX mini-engine.  Both the materialized and the decode-fused answers are
checked against a float64 NumPy reference at rtol=1e-4.

Run:  PYTHONPATH=src python examples/tpch_pipeline.py [--scale 0.01]
"""
import argparse
import os
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.costmodel import CostModel
from repro.data.columns import TABLE2_PLANS
from repro.data.loader import ColumnPipeline
from repro.data.queries import ANSWERS, ENGINES, NUMPY_REFERENCES, QUERY_PLANS
from repro.data.tpch import QUERY_COLUMNS, generate

ap = argparse.ArgumentParser()
ap.add_argument("--scale", type=float, default=0.01)
ap.add_argument("--chunk-kib", type=int, default=1024,
                help="streaming transfer chunk size (KiB); 0 = whole-blob")
ap.add_argument("--chunk-decode", action="store_true",
                help="launch one decode per transferred chunk (element- and "
                     "group-chunkable columns; others fall back to "
                     "whole-column decode)")
ap.add_argument("--policy", default="chunk-johnson",
                choices=["fifo", "johnson", "chunk-johnson", "adaptive"],
                help="scheduling policy for the execution planner; 'adaptive' "
                     "searches orders and chunk configurations by modeled "
                     "makespan")
ap.add_argument("--auto-chunks", action="store_true",
                help="let the planner size chunks per column (overrides "
                     "--chunk-kib)")
ap.add_argument("--cost-cache", default="",
                help="path to a persisted CostModel (JSON): loaded before "
                     "planning so a fresh process plans from calibrated "
                     "history, saved back (updated) on exit")
ap.add_argument("--mesh", type=int, default=0, metavar="N",
                help="shard decode over N logical devices via the "
                     "topology-aware mesh planner (run_sharded); 0 keeps the "
                     "single-device streaming path.  With >1 visible devices "
                     "oversized group-chunkable columns split into per-device "
                     "group-span shards")
ap.add_argument("--async-dispatch", action="store_true",
                help="issue host->device transfers from per-link worker "
                     "threads so multi-device issuance overlaps (mesh path "
                     "decodes shards concurrently)")
ap.add_argument("--placement", default=None, choices=["sharded"],
                help="'sharded' pins shard i of every split column to logical "
                     "device i; the planner may land shards elsewhere and "
                     "rebalance over the D2D fabric when that is modeled "
                     "faster (decode-where-landed)")
args = ap.parse_args()
enable_compile_cache()
chunk_bytes = "auto" if args.auto_chunks else (args.chunk_kib * 1024 or None)

cost_model = None
if args.cost_cache:
    if os.path.exists(args.cost_cache):
        cost_model = CostModel.load(args.cost_cache)
        print(f"cost cache: loaded {args.cost_cache} "
              f"({len(cost_model.sig_stats)} signatures, "
              f"{cost_model.n_observed} prior observations)")
    else:
        cost_model = CostModel()
        print(f"cost cache: {args.cost_cache} not found, starting cold")

cols = generate(scale=args.scale, seed=0)
print(f"generated TPC-H-like tables at scale {args.scale} "
      f"({cols['L_ORDERKEY'].size:,} lineitems)")

for q in (1, 6):
    names = QUERY_COLUMNS[q]
    qcols = {n: cols[n] for n in names}
    raw_bytes = sum(a.nbytes for a in qcols.values())
    # float64 NumPy answer both engines are held to
    ref = NUMPY_REFERENCES[q](qcols)

    pipe = ColumnPipeline({n: TABLE2_PLANS[n] for n in names},
                          chunk_bytes=chunk_bytes,
                          chunk_decode=args.chunk_decode, policy=args.policy,
                          cost_model=cost_model,
                          mesh=args.mesh or None,
                          async_dispatch=args.async_dispatch,
                          placement=args.placement)
    ratios = pipe.compress(qcols)
    comp_bytes = sum(pipe._encoded[n].compressed_nbytes for n in names)
    mesh_res = None
    t0 = time.perf_counter()
    if args.mesh and args.mesh > 1:
        mesh_res = pipe.run_sharded()   # topology-aware per-device windows
        results = mesh_res.columns
    else:
        results = pipe.run()    # planned streaming: order/chunks/modes from plan
    t_move = time.perf_counter() - t0
    device_cols = {n: r.array for n, r in results.items()}
    if mesh_res is not None:
        # the mini-engine is single-device: gather the mesh-landed columns
        # (query-on-mesh stays with the fused per-shard path in core.serve)
        device_cols = {n: jax.device_put(a, jax.devices()[0])
                       for n, a in device_cols.items()}
    t0 = time.perf_counter()
    out = jax.block_until_ready(jax.jit(ENGINES[q])(device_cols))
    t_query = time.perf_counter() - t0
    np.testing.assert_allclose(ANSWERS[q](out), ref, rtol=1e-4)
    print(f"\nTPC-H Q{q}: {raw_bytes / 1e6:.1f} MB raw -> "
          f"{comp_bytes / 1e6:.2f} MB compressed "
          f"({raw_bytes / comp_bytes:.1f}x)")
    for n in names:
        print(f"   {n:18s} ratio {ratios[n]:7.1f}x  "
              f"plan {TABLE2_PLANS[n].describe()}")
    print(f"   movement+decode {t_move * 1e3:.1f} ms, query {t_query * 1e3:.1f} ms"
          f" -> result {np.asarray(out).ravel()[:4]}")
    stats = pipe.cache_stats
    print(f"   programs: {stats['programs']} jitted for {len(names)} columns "
          f"(cache hits {stats['hits']}, evictions {stats['evictions']})")
    if args.chunk_decode:
        launches = {n: r.decode_launches for n, r in results.items()}
        print(f"   per-chunk decode: "
              f"{sum(r.chunk_decoded for r in results.values())}/{len(names)} "
              f"columns chunked, launches {launches}")
    if mesh_res is not None:
        sharded = [n for n, s in mesh_res.plan.shards.items() if len(s) > 1]
        print(f"   mesh x{args.mesh}"
              f"{' (async dispatch)' if args.async_dispatch else ''}: "
              f"sharded {sharded or 'none'}; per-device launches "
              f"{dict(sorted(mesh_res.device_launches.items()))}")
        if mesh_res.d2d_copies:
            legs = ", ".join(f"{it}: d{src}->d{dst} {s * 1e3:.2f}ms"
                             for it, (src, dst, s)
                             in sorted(mesh_res.d2d_copies.items()))
            print(f"   d2d rebalance ({len(mesh_res.d2d_copies)} legs): {legs}")
        elif args.placement:
            print("   d2d rebalance: no legs (decode landed on placement, or "
                  "no fabric modeled)")
        continue  # planner/fused-query reporting below is single-device
    # makespans reuse the timings measured during run() -- no re-measurement
    mk_nopipe = pipe.modeled_makespan(pipeline=False)
    mk_pipe = pipe.modeled_makespan(pipeline=True, johnson=True)
    mk_chunk = pipe.modeled_makespan(pipeline=True, johnson=True, chunked=True)
    print(f"   pipelining: serial {mk_nopipe * 1e3:.1f} ms -> "
          f"Johnson {mk_pipe * 1e3:.1f} ms "
          f"({mk_nopipe / max(mk_pipe, 1e-9):.2f}x) -> "
          f"chunked {mk_chunk * 1e3:.1f} ms "
          f"({mk_nopipe / max(mk_chunk, 1e-9):.2f}x)")
    # re-plan from the measured timings: planned vs measured makespan
    ep = pipe.plan()
    print(f"   planner ({ep.policy}): planned {ep.modeled_makespan_s * 1e3:.1f} "
          f"ms vs measured move+decode {t_move * 1e3:.1f} ms; baselines "
          + " ".join(f"{k}={v * 1e3:.1f}ms" for k, v in sorted(ep.baselines.items())))
    for line in ep.explain().splitlines():
        print(f"     {line}")
    # decode-fused execution (late materialization): the query's operators
    # ride the per-chunk decode launches -- only partial aggregates hit HBM
    qp = QUERY_PLANS[q]
    qe = pipe.run_query(qp)         # cold call traces the chunk programs
    t0 = time.perf_counter()
    qe = pipe.run_query(qp)
    t_fused = time.perf_counter() - t0
    np.testing.assert_allclose(ANSWERS[q](qe.result), ref, rtol=1e-4)
    ep_q = pipe.query_plan(qp)
    n_fused = sum(d.fused for d in ep_q.decisions.values())
    print(f"   decode-fused Q{q}: {t_fused * 1e3:.1f} ms warm (cold "
          f"materialize+query above: {(t_move + t_query) * 1e3:.1f} ms); "
          f"selectivity "
          f"{qe.selectivity:.4f}; {qe.n_chunks} chunks / "
          f"{qe.decode_launches} launches; HBM traffic "
          f"{qe.traffic_bytes / 1e6:.2f} MB (pre-fusion "
          f"{qe.prefuse_traffic_bytes / 1e6:.2f} MB); "
          f"{qe.plain_bytes / 1e6:.2f} MB of decoded rows never written; "
          f"planner fused {n_fused}/{len(names)} columns")

if args.cost_cache and cost_model is not None:
    cost_model.save(args.cost_cache)
    print(f"\ncost cache: saved {args.cost_cache} "
          f"({len(cost_model.sig_stats)} signatures, "
          f"{cost_model.n_observed} observations)")
