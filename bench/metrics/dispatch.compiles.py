"""Jitted simjoin entry points traced inside the window (the program's
``ops.TRACE_COUNTS``): each is a compile or a load from the persistent
cache. The warm-up aims to leave none."""


def read(run):
    """Traces counted over the window."""
    return run.compiles
