"""Share of kernel launches whose batch was served from the program's
pinned device stacks (%): the ``dispatch_pinned_hits`` counter summed
over the window's ``query`` spans, over their ``dispatch_launches``;
0 where no query launched a kernel."""
from bench.program_counters import recorded


def read(run):
    """Pinned-stack hits over launches, times 100."""
    hits = recorded(run, "dispatch_pinned_hits")
    launches = recorded(run, "dispatch_launches")
    if hits is None or launches is None:
        return None
    return 100.0 * hits / launches if launches else 0.0
