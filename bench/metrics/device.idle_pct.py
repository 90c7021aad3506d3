"""Share of the traced window in which no operation ran on the device
(%), averaged over the devices the cell uses."""


def read(run):
    """One minus busy over window, in %."""
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
