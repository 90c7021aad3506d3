"""Bytes placed on the device per query for batches that missed the
pinned-batch cache (MB, 10^6 bytes): the program's
``dispatch_put_bytes`` counter, from the window's ``query`` spans."""
from bench.program_spans import counter


def read(run):
    """Placed MB per query."""
    return counter(run, "dispatch_put_bytes", 1e-6)
