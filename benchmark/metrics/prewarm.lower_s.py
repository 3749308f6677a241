"""The span ``prewarm_lower``: ``fn.lower(*avals)`` on the prewarm thread,
tracing and lowering of the step. ``prewarm.compile_s`` less this is the
build or the cache read."""
from benchmark import programs


def read(ctx):
    return programs.of(ctx).span_s("prewarm_lower")
