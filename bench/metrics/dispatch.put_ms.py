"""Time per query in ``dispatch.put`` spans (ms): placing the stacked
batches of pinned-cache misses on the device and waiting for them."""
from bench.program_spans import span_ms


def read(run):
    """Milliseconds in ``dispatch.put`` per query."""
    return span_ms(run, "dispatch.put")
