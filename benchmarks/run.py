"""Benchmark harness: one module per paper table/figure.

``python -m benchmarks.run [--quick] [--only fig12,fig19]``
prints ``name,us_per_call,derived`` CSV rows (the scaffold contract).
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.compile_cache import enable_compile_cache

from benchmarks import (ckpt_grad, fig12_bitpack, fig13_rle, fig14_ans,
                        fig15_ans_chunks, fig16_tpch_ratio,
                        fig17_tpch_throughput, fig18_fusion, fig19_e2e,
                        fig22_geometry, roofline_table)

MODULES = {
    "fig12": fig12_bitpack, "fig13": fig13_rle, "fig14": fig14_ans,
    "fig15": fig15_ans_chunks, "fig16": fig16_tpch_ratio,
    "fig17": fig17_tpch_throughput, "fig18": fig18_fusion,
    "fig19": fig19_e2e, "fig22": fig22_geometry,
    "roofline": roofline_table, "ckpt_grad": ckpt_grad,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweeps (CI-sized)")
    ap.add_argument("--only", default=None,
                    help="comma-separated module keys")
    args = ap.parse_args()
    enable_compile_cache()
    keys = args.only.split(",") if args.only else list(MODULES)
    print("name,us_per_call,derived")
    t0 = time.time()
    for key in keys:
        mod = MODULES[key]
        print(f"# --- {key} ({mod.__doc__.splitlines()[0].strip()}) ---",
              flush=True)
        try:
            mod.main(quick=args.quick)
        except Exception as e:  # noqa: BLE001 -- keep the harness running
            print(f"{key}/ERROR,0,{type(e).__name__}: {str(e)[:120]}",
                  file=sys.stderr)
            raise
    print(f"# total {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
