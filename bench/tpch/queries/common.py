"""Helpers shared by the query modules."""
from __future__ import annotations

import numpy as np

BLOCK = 4096    # rows per partial sum in the bfloat16 control


def rel_error(got, want) -> float:
    """Largest |got - want| / |want| over the entries of two answers of one
    shape; ``inf`` when the shapes differ (a group missing or extra)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    if not np.all(np.isfinite(got)):
        return float("inf")
    den = np.abs(want)
    err = np.abs(got - want)
    if np.any((den == 0) & (err != 0)):
        return float("inf")
    nz = den != 0
    return float(np.max(err[nz] / den[nz])) if nz.any() else 0.0


def blocked_sum(v, dtype):
    """Sum of a 1-D ``jax.numpy`` array in ``dtype``: partials over BLOCK
    rows, then the sum of the partials, each rounded to ``dtype``."""
    import jax.numpy as jnp

    v = v.astype(dtype)
    pad = -v.shape[0] % BLOCK
    parts = jnp.pad(v, (0, pad)).reshape(-1, BLOCK).sum(1, dtype=dtype)
    return parts.sum(dtype=dtype)
