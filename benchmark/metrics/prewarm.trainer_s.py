"""The span ``prewarm_trainer``: the prewarm thread builds the objective and
the trainer whose step it compiles (``prewarm.step_spec`` included)."""
from benchmark import programs


def read(ctx):
    return programs.of(ctx).span_s("prewarm_trainer")
