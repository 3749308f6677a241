"""Level passes an iteration: launches of the level's histogram kernel (the
``hist_path`` event's ``level_kernel``: ``hist_leaf_q8`` above F x B = 2,048,
``hist_level_q8`` below) under a ``level_s<W>`` scope, so the root pass of a
tree is not counted, nor the route-only last level. K class trees of a
depth-capped shape take K x (``max_depth`` - 1): 49.0 in every run is what
says the passes do not depend on the data."""
from benchmark import scopes, scopes_multiclass as mc


def read(ctx):
    view = mc.view_of(ctx)
    path = mc.last_event(ctx, "hist_path")
    if view is None or path is None:
        return None
    n = mc.launches_under(view, (path["level_kernel"],),
                          lambda p: bool(scopes.LEVEL.fullmatch(p)))
    return n / view.n_iters if n else None
