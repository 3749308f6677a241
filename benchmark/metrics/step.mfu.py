"""Least time the chip could take for one iteration's work (work.py, from
shapes; peaks.py) over the window's wall time per iteration, in percent."""


def read(ctx):
    if ctx.peaks is None:
        return None
    cfg = ctx.cell["cfg"]
    wk = ctx.work.iteration_work(ctx.n_train, cfg["num_features"],
                                 cfg["params"]["num_leaves"], ctx.channels,
                                 ctx.n_valid)
    least, _ = ctx.work.least_seconds(wk, ctx.peaks)
    return 100.0 * least / (ctx.window_s / ctx.window.window_iters)
