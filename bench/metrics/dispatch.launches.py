"""Jitted kernel calls per query: the program's ``dispatch_launches``
counter (one call per shape-bucketed batch), from the window's
``query`` spans."""
from bench.program_spans import counter


def read(run):
    """Kernel launches per query."""
    return counter(run, "dispatch_launches")
