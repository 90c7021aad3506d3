"""Share of join-artifact lookups that missed the program's
``JoinArtifactCache`` in the window (%)."""


def read(run):
    """Misses over hits plus misses; nothing when no lookup happened."""
    miss = sum(r["artifact_misses"] or 0 for r in run.records)
    hit = sum(r["artifact_hits"] or 0 for r in run.records)
    return 100.0 * miss / (hit + miss) if hit + miss else None
