"""Time to combine a row-sharded query's partial aggregates, in ms per
query: the seconds of the traced window's ``zipflow.combine`` spans (the
sum across chips on the device and the one D2H of the result), over the
scans completed.  Nothing where the program has no such span."""
from bench import spans


def read(run):
    reg = spans.registry()
    if reg is None or "zipflow.combine" not in reg:
        return None
    v = spans.per_scan(run, ("zipflow.combine",))
    return None if v is None else 1e3 * v
