"""The simjoin kernels' share of their HBM roofline (%).

The least time the chip could take for a query's join is its queried
cells read once from HBM: ``cells * d * 4`` bytes (int32 coordinates)
over the published HBM bandwidth. That depends only on the query's
input, not on how it is chunked, pruned or tiled. The share is that
bound, summed over the window's queries, over the kernels' device time
from the trace. No int32 vector peak is published for the chip, so the
compute bound is left out and the share is a lower bound of the true
roofline share."""


def read(run):
    """HBM-bound seconds over kernel device seconds, in %."""
    if run.trace is None or run.trace["kernel_s"] <= 0:
        return None
    d = len(run.config["domain_hi"])
    nbytes = sum(r["queried_cells"] * d * 4 for r in run.records)
    bound_s = nbytes / (run.peak["hbm_bytes_per_s"])
    return 100.0 * bound_s / run.trace["kernel_s"]
