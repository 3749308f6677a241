"""Least time for the histogram work of K class trees (K x work.hist_work,
from shapes: N rows x the configuration's raw columns, the same whatever
implements it; bytes bind) over the device time of the Mosaic kernels in the
step an iteration (``kernels.hist_ms_per_iter``'s reading), in percent."""
from benchmark import scopes_multiclass as mc
from benchmark.trace import MOSAIC, STEP_MODULE


def read(ctx):
    k = mc.num_class(ctx)
    if not k or ctx.trace is None or ctx.peaks is None:
        return None
    t = ctx.trace.op_s(MOSAIC, STEP_MODULE)
    if not t:
        return None
    cfg = ctx.cell["cfg"]
    wk = ctx.work.hist_work(ctx.n_train, cfg["num_features"],
                            cfg["params"]["num_leaves"], ctx.channels)
    least, _ = ctx.work.least_seconds({n: k * v for n, v in wk.items()},
                                      ctx.peaks)
    return 100.0 * least / (t / ctx.trace.n_iters)
