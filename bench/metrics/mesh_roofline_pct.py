"""A row-sharded scan's share of the host's HBM roofline: the least time
the bytes the work needs take at the peak HBM rate of every chip the scan
used (``bench/peaks.json``), over the device's busy time in the traced
window.  The bytes are those of ``decode_roofline_pct``; the chips are the
program's ``mesh_chips`` counter per scan completed; busy time is already
averaged over the device planes that ran an op, so the share cannot pass
100 %.  Nothing where the program has no such counter."""
from bench import spans


def chips_per_scan(run) -> float | None:
    """Chips a scan used on average in the window, or None."""
    reg = spans.registry()
    if reg is None or not reg.get("mesh_chips") or run.scans == 0:
        return None
    return reg["mesh_chips"] / run.scans


def read(run):
    chips = chips_per_scan(run)
    if (chips is None or run.trace is None or run.peaks is None
            or run.needed_bytes == 0):
        return None
    least_s = run.needed_bytes / (chips * float(run.peaks["hbm_bytes_per_s"]))
    return 100.0 * least_s / run.trace["busy_s"]
