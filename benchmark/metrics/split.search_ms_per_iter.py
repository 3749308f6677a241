"""Device time under the scope ``split_search``, per iteration: the reading
of ``split.device_ms_per_iter``, whose list of cells is closed, by its own
reader."""
from benchmark import harness


def read(ctx):
    return harness.read_metric("split.device_ms_per_iter", ctx)
