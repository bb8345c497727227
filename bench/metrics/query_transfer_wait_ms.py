"""Mean ``QueryExec.transfer_s`` per fused query, in ms: host time in the
query's ``device_put`` calls plus the wait for each chunk's pieces."""


def read(run):
    if not run.transfer_s:
        return None
    return 1e3 * sum(run.transfer_s) / len(run.transfer_s)
