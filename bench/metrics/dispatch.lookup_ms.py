"""Time per query in ``dispatch.lookup`` spans (ms): finding each
batch's device-placed stack in the pinned-batch cache."""
from bench.program_spans import span_ms


def read(run):
    """Milliseconds in ``dispatch.lookup`` per query."""
    return span_ms(run, "dispatch.lookup")
