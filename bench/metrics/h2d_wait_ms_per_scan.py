"""Host time blocked on staged inputs per query or stream, in ms: the
seconds of the traced window's ``zipflow.wait_h2d`` spans, over the scans
completed."""
from bench import spans


def read(run):
    v = spans.per_scan(run, ("zipflow.wait_h2d",))
    return None if v is None else 1e3 * v
