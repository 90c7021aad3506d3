"""Time per query in ``dispatch.launch`` spans (ms): the jitted kernel
call until it returns (Python and launch overhead; the device runs
asynchronously)."""
from bench.program_spans import span_ms


def read(run):
    """Milliseconds in ``dispatch.launch`` per query."""
    return span_ms(run, "dispatch.launch")
