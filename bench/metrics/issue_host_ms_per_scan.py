"""Host time of the issue loop per query or stream, in ms: the root spans'
seconds (``zipflow.stream``, ``zipflow.query``; a root nested in another
counts once) less those of the ``zipflow.wait_h2d``, ``zipflow.wait_decode``
and ``zipflow.plan`` spans inside them -- staging, puts, launches, waits for
a worker's put, the loop's own time and finalize -- over the scans
completed.  In a warm window every such span runs inside a root on the
root's thread."""
from bench import spans


def read(run):
    v = spans.per_scan(run, spans.ROOTS, less=spans.WAITS)
    return None if v is None else 1e3 * v
