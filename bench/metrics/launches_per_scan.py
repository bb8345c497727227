"""Decode launches per query or stream: ``QueryExec.decode_launches`` plus
its resident columns' launches, or a stream's ``ColumnExec.decode_launches``
with batched columns counted once."""


def read(run):
    return None if run.scans == 0 else run.launches / run.scans
