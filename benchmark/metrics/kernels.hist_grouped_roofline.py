"""Least time for the histogram work alone (work.hist_work, from shapes;
bytes bind at these shapes) over the device time of the grouped kernel
``hist_leaf_q8``, in percent."""
from benchmark import scopes


def read(ctx):
    view = scopes.of(ctx)
    if view is None or ctx.peaks is None:
        return None
    _, t = view.kernel("hist_leaf_q8")
    if not t:
        return None
    cfg = ctx.cell["cfg"]
    wk = ctx.work.hist_work(ctx.n_train, cfg["num_features"],
                            cfg["params"]["num_leaves"], ctx.channels)
    least, _ = ctx.work.least_seconds(wk, ctx.peaks)
    return 100.0 * least / (t / view.n_iters)
