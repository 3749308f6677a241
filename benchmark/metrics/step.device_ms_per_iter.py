"""Device time of the ``jit_step`` module, per iteration."""
from benchmark.trace import STEP_MODULE


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.per_iter_ms(ctx.trace.module_s(STEP_MODULE))
