"""Process start to the first timed query (s): data generation, the
catalog's raw files, the cluster, the warm-up pass and every kernel
compile or cache load it drives."""


def read(run):
    """The run's set-up seconds."""
    return run.setup_s
