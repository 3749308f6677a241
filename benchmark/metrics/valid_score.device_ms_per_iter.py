"""Device time of validation scoring, per iteration: the ``jit_route_bins``
module and the eager ``take_small`` (a Pallas call dispatched on its own,
which the trace names ``jit_wrapped``; nothing else in the window does)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.per_iter_ms(
        ctx.trace.module_s("jit_route_bins", "jit_wrapped"))
