"""Time per query in ``dispatch.wait`` spans (ms): blocking until each
launched kernel's result is ready."""
from bench.program_spans import span_ms


def read(run):
    """Milliseconds in ``dispatch.wait`` per query."""
    return span_ms(run, "dispatch.wait")
