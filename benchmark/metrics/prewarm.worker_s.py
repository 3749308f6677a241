"""The prewarm thread's life: the package's span ``prewarm_worker``."""
from benchmark import programs


def read(ctx):
    return programs.of(ctx).span_s("prewarm_worker")
