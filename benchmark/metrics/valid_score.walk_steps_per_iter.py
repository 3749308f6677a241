"""Steps the validation walk took, per iteration: the package's
``valid_walk`` events (one per tree walked over a validation set, ``steps``
counted on the device by the walk itself) of the window's iterations. A
balanced 255-leaf tree takes eight, as many as ``grower.level_passes_per_iter``
counts level passes; a program whose walk has a fixed trip count emits no
such event and the line leaves the metric out."""


def read(ctx):
    walks = [e for e in ctx.obs_events if e.get("type") == "valid_walk"]
    if not walks:
        return None
    first = ctx.window.warmup + 1
    steps = sum(e["steps"] for e in walks
                if first <= e["iteration"] < first + ctx.window.window_iters)
    return steps / ctx.window.window_iters
