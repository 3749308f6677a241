"""Validation walks that ran as the Mosaic kernel (``walk_tree``), per
iteration: the package's ``valid_walk`` events of the window's iterations
whose ``path`` is ``"kernel"``. 1.0 where one validation set is scored by one
tree an iteration and the kernel engaged; 0.0 where every walk took the XLA
program; a program whose events carry no ``path`` (or that emits none) leaves
the metric out of the line."""


def read(ctx):
    walks = [e for e in ctx.obs_events
             if e.get("type") == "valid_walk" and "path" in e]
    if not walks:
        return None
    first = ctx.window.warmup + 1
    kernel = sum(e["path"] == "kernel" for e in walks
                 if first <= e["iteration"] < first + ctx.window.window_iters)
    return kernel / ctx.window.window_iters
