"""How far the fullest chip's rows stand above an even split, in %:
100 x (``mesh_rows_full`` / ``mesh_rows`` - 1) over the traced window, the
program's counters of a row-sharded query (the fullest chip's rows times
the chips that got rows, and the rows summed over chips).  0 when every
chip scans as many rows as the fullest.  Nothing where the program has no
such counters or scanned no rows on a mesh."""
from bench import spans


def read(run):
    reg = spans.registry()
    if reg is None or not reg.get("mesh_rows"):
        return None
    return 100.0 * (reg["mesh_rows_full"] / reg["mesh_rows"] - 1.0)
