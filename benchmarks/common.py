"""Shared benchmark helpers.

Wall-clock numbers are times on whatever platform JAX runs on: a CPU run says
nothing about a TPU, and no figure here has been measured on a chip yet
(``chip_smoke.py`` is the chip run).  Each benchmark also derives modeled-TPU
quantities (bytes moved, roofline throughput) so the table structure matches the
paper's figures.  Output format: ``name,us_per_call,derived``.
"""
from __future__ import annotations

import time
from typing import Callable

import jax
import numpy as np


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall-clock seconds per call of a jitted function."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def row(name: str, seconds: float, derived: str = "") -> str:
    line = f"{name},{seconds * 1e6:.1f},{derived}"
    print(line, flush=True)
    return line


def gbps(nbytes: int, seconds: float) -> float:
    return nbytes / max(seconds, 1e-12) / 1e9


def modeled_tpu_throughput_gbps(plain_bytes: int, compressed_bytes: int,
                                hbm_gbps: float = 819.0) -> float:
    """Paper Eq. 1: GpuMemBandwidth * plain / (compressed + plain)."""
    return hbm_gbps * plain_bytes / (compressed_bytes + plain_bytes)
