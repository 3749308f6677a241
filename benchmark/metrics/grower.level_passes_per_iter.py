"""Fused route+histogram level passes per iteration: events of the kernel
``hist_level_q8`` inside the step. A balanced 255-leaf tree takes seven
(its last level routes its rows in ``route_level`` alone and builds no
histogram); later, less balanced trees take more."""
from benchmark import scopes


def read(ctx):
    view = scopes.of(ctx)
    if view is None:
        return None
    n, _ = view.kernel("hist_level_q8")
    return n / view.n_iters if n else None
