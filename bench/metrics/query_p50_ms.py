"""Median latency of the queries completed in the window, on the
client's clock (ms)."""
import statistics


def read(run):
    """Median of every query's latency."""
    return statistics.median(r["latency_s"] for r in run.records) * 1e3
