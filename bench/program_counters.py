"""Per-query counters that a program records only from a given version
on, for the metric readers of a traced run.

``bench/program_spans.py`` reads a counter missing from the ``query``
spans as 0. A counter that no ``query`` span of the window carries is
one the program does not keep, and reads ``None`` here, so that a run
of an older program leaves the metric out instead of reporting 0.
"""
from typing import Optional

from bench.program_spans import counter, window


def recorded(run, key: str, scale: float = 1.0) -> Optional[float]:
    """The query counter ``key`` per query of the window, times
    ``scale``; ``None`` where the program keeps no recording or no
    ``query`` span carries the counter."""
    spans = window(run)
    if spans is None:
        return None
    queries = [s for s in spans if s.name == "query" and s.parent_id is None]
    if queries and not any(key in (s.args or {}) for s in queries):
        return None
    return counter(run, key, scale)
