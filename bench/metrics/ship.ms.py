"""Time per query in ``ship`` spans (ms): the replay of the join plan's
transfer routes, each cross-device route a waited device-to-device
copy and each just-scanned payload first staged from the host. Routes
between nodes on one chip move nothing."""
from bench.program_spans import span_ms


def read(run):
    """Milliseconds in ``ship`` per query."""
    return span_ms(run, "ship")
