"""Plain GB per second over the window: the plain bytes of the columns that
every completed query or stream read (rows x itemsize of the generated
columns), over the time from the window's first issue to the completion of
its last unit (host clock, each unit ended by ``block_until_ready``)."""


def read(run):
    if run.window_s <= 0 or run.scans == 0:
        return None
    return run.plain_bytes / run.window_s / 1e9
