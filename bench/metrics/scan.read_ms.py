"""Time per query in ``scan.read`` spans (ms): reading and decoding raw
files from disk. Files are memoized after their first read, so a warm
window reads 0."""
from bench.program_spans import span_ms


def read(run):
    """Milliseconds in ``scan.read`` per query."""
    return span_ms(run, "scan.read")
