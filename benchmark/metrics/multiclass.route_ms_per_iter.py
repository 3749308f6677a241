"""Device time under the scope ``route`` over the K class trees of an
iteration (the row router as a pass of its own ahead of the grouped
histogram kernel, the bundled columns' bin-subset decode in it), per
iteration."""
from benchmark import scopes_multiclass as mc


def read(ctx):
    return mc.scope_ms_per_iter(ctx, "route")
