"""Raw bytes read from disk per query (MB, 10^6 bytes): the program's
``scan_read_bytes`` counter, from the window's ``query`` spans."""
from bench.program_spans import counter


def read(run):
    """Read MB per query."""
    return counter(run, "scan_read_bytes", 1e-6)
