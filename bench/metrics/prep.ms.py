"""Host prep per query (ms): the program's ``prep_s`` (sort, prune,
bitmaps, padding, stacking), averaged over the window; a query that
joined nothing counts 0."""


def read(run):
    """Mean prep seconds per query, in ms."""
    return sum(r["prep_s"] or 0.0 for r in run.records) / len(
        run.records) * 1e3
