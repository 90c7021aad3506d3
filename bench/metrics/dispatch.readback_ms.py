"""Time per query in ``dispatch.readback`` spans (ms): copying each
batch's counts to the host and scattering them to their tasks."""
from bench.program_spans import span_ms


def read(run):
    """Milliseconds in ``dispatch.readback`` per query."""
    return span_ms(run, "dispatch.readback")
