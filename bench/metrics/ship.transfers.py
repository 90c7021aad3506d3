"""Device-to-device transfers per query: the program's
``ship_transfers`` counter (one per transfer route whose nodes lie on
different devices), from the window's ``query`` spans."""
from bench.program_counters import recorded


def read(run):
    """Transfers per query."""
    return recorded(run, "ship_transfers")
