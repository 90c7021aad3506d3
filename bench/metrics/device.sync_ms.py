"""Time per query in ``device.sync`` spans (ms): the post-round
reconcile of committed device buffers with cache residency."""
from bench.program_spans import span_ms


def read(run):
    """Milliseconds in ``device.sync`` per query."""
    return span_ms(run, "device.sync")
