"""The program's own spans and counters (``repro.obs``), as the per-layer
metrics read them.

``repro.obs`` sums, while a profiler session is active, the seconds of each
span name and each counter's increments.  A traced run holds one session,
the measured window, so the registry after it holds the window's totals.
Readers divide them by the scans (queries or streams) completed.

The benchmark also runs program versions from before ``repro.obs``; there
``registry()`` gives None and the metrics are left out of the line.
"""
from __future__ import annotations

ROOTS = ("zipflow.stream", "zipflow.query")
WAITS = ("zipflow.wait_h2d", "zipflow.wait_decode", "zipflow.plan")


def registry() -> dict[str, float] | None:
    """``repro.obs.snapshot()``, or None where the program has no registry."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.snapshot()


def per_scan(run, names, less=()) -> float | None:
    """The registry's sum over ``names`` less its sum over ``less``, per
    scan completed; None without a registry or a completed scan."""
    reg = registry()
    if reg is None or run.scans == 0:
        return None
    total = sum(reg.get(n, 0.0) for n in names) - \
        sum(reg.get(n, 0.0) for n in less)
    return total / run.scans
