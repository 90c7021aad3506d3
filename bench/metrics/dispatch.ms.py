"""Dispatch per query (ms): the program's ``dispatch_s`` (device
placement of the batches, kernel launch and the wait for its result),
averaged over the window; a query that joined nothing counts 0."""


def read(run):
    """Mean dispatch seconds per query, in ms."""
    return sum(r["dispatch_s"] or 0.0 for r in run.records) / len(
        run.records) * 1e3
