"""Route-only level passes an iteration: launches of the ``route_level``
kernel under the device scope ``route_only`` inside the step. A level after
which no level can run (its splits fill the leaf budget, or the depth cap
allows no deeper level) routes its rows there and builds no histograms, so
every tree that ends on its budget or its depth takes one: 1.0 with one
tree an iteration, K with K class trees. Silent from a program without the
scope."""
from benchmark import scopes, scopes_multiclass as mc


def read(ctx):
    view = scopes.of(ctx)
    if view is None:
        return None
    n = mc.launches_under(view, ("route_level",), lambda p: p == "route_only")
    return n / view.n_iters if n else None
