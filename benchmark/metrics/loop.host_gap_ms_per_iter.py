"""Device-idle time of the traced window, per iteration."""


def read(ctx):
    if ctx.trace is None:
        return None
    return (ctx.trace.window_s - ctx.trace.busy_s) * 1e3 / ctx.trace.n_iters
