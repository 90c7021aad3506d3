"""Bytes the device reconcile materialized, moved or copied per query
(MB, 10^6 bytes): the program's ``device_sync_bytes`` counter, from
the window's ``query`` spans."""
from bench.program_spans import counter


def read(run):
    """Synced MB per query."""
    return counter(run, "device_sync_bytes", 1e-6)
