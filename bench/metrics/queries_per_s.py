"""Queries completed over the whole window, per second of it."""


def read(run):
    """Completed queries over the window's length."""
    done = sum(1 for r in run.records if not r["failed"])
    return done / run.window_s
