"""Planner time per query or stream, in ms: the seconds of the traced
window's ``zipflow.plan`` spans (``StreamingExecutor.plan`` and a cold fused
query's chunk-ladder search), over the scans completed."""
from bench import spans


def read(run):
    v = spans.per_scan(run, ("zipflow.plan",))
    return None if v is None else 1e3 * v
