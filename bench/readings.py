"""Readings that set a cell's limits: the program's compared numbers on
many seeds, and the control's, in one process so set-up is paid once.

    python3 bench/readings.py --workload <cell> --program <seeds> \
        --control <seeds> [--rehearse] [--store-dir d]

Both drive the cell's timed path as a run does (stored blobs,
``ColumnPipeline``, warm-up, one unit of the traffic) and print the numbers
the run compares.  ``--control`` plants ``control_hook`` in it: the
reference takes the program's place, computed a step below the precision
the configuration states (bfloat16 for the float32 query answers, int16 for
int32 columns whose guarantee is lossless decode).  One JSON line per
reading; the benchmark's own runs never run this.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

if __name__ == "__main__":
    _root = Path(__file__).resolve().parents[1]
    sys.path[0:1] = [str(_root), str(_root / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse
import json
import time

import numpy as np

from bench import harness, store


def control_hook(cell, cols):
    """``hooks(pipe)`` that puts the control in the program's place: the
    unit's pipeline still runs, and each fused answer, or each materialized
    column, it hands back is the control's, made from ``cols``."""
    import jax.numpy as jnp

    made: dict = {}
    if cell.traffic["unit"] == "query_round":
        mods = {q: harness.load_module(harness.find("query", q))
                for q in cell.traffic["queries"]}

        def hooks(pipe):
            orig = pipe.run_query

            def run_query(qplan, **kw):
                qe = orig(qplan, **kw)
                if qplan.name not in made:
                    mod = mods[qplan.name]
                    made[qplan.name] = mod.control(
                        {c: cols[c] for c in mod.COLUMNS}, jnp.bfloat16)
                qe.result = made[qplan.name]
                return qe
            pipe.run_query = run_query
        return hooks

    def narrow(a):
        x = jnp.asarray(a)
        return x.astype(jnp.int16).astype(x.dtype) if a.dtype == np.int32 \
            else x

    def hooks(pipe):
        orig = pipe.run

        def run(*a, **kw):
            res = orig(*a, **kw)
            for n, rec in res.items():
                if n not in made:
                    made[n] = narrow(cols[n])
                rec.array = made[n]
            return res
        pipe.run = run
    return hooks


def reading(cell, encoded, seed, rehearse, compiles, hooks=None) -> dict:
    """One unit of the cell's traffic after warm-up, and the numbers its run
    would compare."""
    t = time.perf_counter()
    unit = harness.build_unit(cell, encoded, hooks)
    harness.warm_up(unit, compiles)
    run = harness.Run()
    attempted, failed, error = harness.window(unit, run, 0, compiles, t)
    harness.release(unit)
    checks = {}
    if error is None:
        cols = harness.source_columns(cell.config, seed, rehearse)
        checks = unit.checks(cols, cell.config["limits"])
    return {"workload": cell.name, "seed": seed, "attempted": attempted,
            "failed": failed, "window_s": run.window_s,
            "checks": {n: v for n, (v, _) in checks.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program", default="",
                    help="comma-separated seeds for the program's readings")
    ap.add_argument("--control", default="",
                    help="comma-separated seeds for the control's readings")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--store-dir", type=Path, default=None)
    args = ap.parse_args(argv)
    seeds = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    cell = harness.load_cell(harness.ROOT / "BENCHMARK.json", args.workload,
                             False)
    cfg = cell.config
    store_root = args.store_dir or store.DEFAULT_ROOT
    # every store first: encoding happens before this process opens a device
    stores = {s: harness.host_data(cfg, s, args.rehearse, store_root)
              for s in seeds(args.program) + seeds(args.control)}
    opened = harness.open_devices(cell.chips, args.rehearse)
    if opened is None:
        return 2
    compiles = harness.Compiles()
    for kind, group in (("program", seeds(args.program)),
                        ("control", seeds(args.control))):
        for seed in group:
            encoded, _ = stores[seed]
            hooks = None
            if kind == "control":
                cols = harness.source_columns(cfg, seed, args.rehearse)
                hooks = control_hook(cell, cols)
            out = reading(cell, encoded, seed, args.rehearse, compiles, hooks)
            print(json.dumps({"kind": kind, **out}), flush=True)
            hooks = cols = None
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
