"""Device time of the Mosaic custom calls inside the step, per iteration."""
from benchmark.trace import MOSAIC, STEP_MODULE


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.per_iter_ms(ctx.trace.op_s(MOSAIC, STEP_MODULE))
