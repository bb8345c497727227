"""Decode's share of the HBM roofline: the least time the bytes the work
needs take at the chip's peak HBM rate (``bench/peaks.json``), over the
device's busy time in the traced window.  The bytes are the compressed bytes
of every column each completed unit read, plus the plain bytes of the
outputs it materialized (a stream writes every column; a fused query writes
none), counted by the benchmark from the store and the generated columns."""


def read(run):
    if run.trace is None or run.peaks is None or run.needed_bytes == 0:
        return None
    least_s = run.needed_bytes / float(run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / run.trace["busy_s"]
