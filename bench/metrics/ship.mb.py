"""Bytes shipped between devices per query (MB, 10^6 bytes): the
program's ``measured_ship_bytes`` counter, from the window's ``query``
spans."""
from bench.program_counters import recorded


def read(run):
    """Shipped MB per query."""
    return recorded(run, "measured_ship_bytes", 1e-6)
