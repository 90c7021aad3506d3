"""Device time of the simjoin kernels per query (ms), from the
profiler's trace of the window, summed over the devices."""


def read(run):
    """Kernel device seconds over completed queries, in ms."""
    if run.trace is None or run.trace["kernel_s"] <= 0:
        return None
    return run.trace["kernel_s"] / len(run.records) * 1e3
