"""Plans per query or stream whose issue order or per-column decisions
differ from the previous plan over the same columns: the program's
``plan_changes`` counter over the traced window, over the scans
completed."""
from bench import spans


def read(run):
    return spans.per_scan(run, ("plan_changes",))
