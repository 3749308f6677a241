"""Least time for the histogram work alone (work.py; bytes bind at these
shapes) over the Mosaic kernels' device time, in percent. Silent where no
Mosaic kernel ran."""
from benchmark.trace import MOSAIC, STEP_MODULE


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    t = ctx.trace.op_s(MOSAIC, STEP_MODULE)
    if not t:
        return None
    cfg = ctx.cell["cfg"]
    wk = ctx.work.hist_work(ctx.n_train, cfg["num_features"],
                            cfg["params"]["num_leaves"], ctx.channels)
    least, _ = ctx.work.least_seconds(wk, ctx.peaks)
    return 100.0 * least / (t / ctx.trace.n_iters)
