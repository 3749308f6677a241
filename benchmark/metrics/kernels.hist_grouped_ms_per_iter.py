"""Device time of the grouped histogram kernel ``hist_leaf_q8`` (a grid of
feature groups x row chunks; the root pass and every level pass of data wider
than one accumulator block), per iteration."""
from benchmark import scopes


def read(ctx):
    view = scopes.of(ctx)
    if view is None:
        return None
    return view.per_iter_ms(view.kernel("hist_leaf_q8")[1])
