"""Device time of the AUC's programs (argsort, scatter-add, gather), per
iteration."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.per_iter_ms(
        ctx.trace.module_s("jit_argsort", "jit_scatter-add", "jit_gather"))
