"""The program's own spans and per-query counters over a run's window,
for the metric readers of a traced run.

The program keeps its most recent recording telemetry bundle
(``repro.obs.last_recording()``) after the cluster that used it is gone.
In a traced run the window's queries are the last ``len(run.records)``
root ``query`` spans of its tracer, and every span that began at or
after the first of them belongs to the window: nothing runs after the
window. Each ``query`` span carries the query's counters as arguments
(``dispatch_launches``, ``dispatch_put_bytes``, ``device_sync_bytes``,
``scan_read_bytes``).

Each reading is per query of the window. It is ``None`` where the
program keeps no such bundle (a program without these spans), and 0
where the work did not happen.
"""
from typing import List, Optional


def window(run) -> Optional[List]:
    """The closed spans of the run's window, or ``None`` where the
    program cannot hand them over."""
    try:
        from repro.obs.telemetry import last_recording
    except ImportError:
        return None
    telemetry = last_recording()
    if telemetry is None:
        return []
    spans = telemetry.tracer.spans
    queries = [s for s in spans if s.name == "query" and s.parent_id is None]
    if not queries:
        return []
    first = queries[-len(run.records):][0].start
    return [s for s in spans if s.start >= first and s.end is not None]


def span_ms(run, name: str) -> Optional[float]:
    """Milliseconds spent in spans named ``name``, per query."""
    spans = window(run)
    if spans is None:
        return None
    total = sum(s.end - s.start for s in spans if s.name == name)
    return total / len(run.records) * 1e3


def counter(run, key: str, scale: float = 1.0) -> Optional[float]:
    """The query counter ``key`` summed over the window's ``query``
    spans, per query, times ``scale``."""
    spans = window(run)
    if spans is None:
        return None
    total = sum((s.args or {}).get(key, 0) for s in spans
                if s.name == "query" and s.parent_id is None)
    return total / len(run.records) * scale
