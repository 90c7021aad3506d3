"""Planner time per query (ms): the program's own chunking and
eviction/placement clocks (``opt_time_chunking_s`` +
``opt_time_evict_place_s``), averaged over the window."""


def read(run):
    """Mean planner seconds per query, in ms."""
    return sum(r["opt_s"] for r in run.records) / len(run.records) * 1e3
