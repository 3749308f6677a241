"""Device time under the scope ``split_search`` (best split of every
frontier leaf, forced and monotone fix-ups, the budgeted selection), per
iteration."""
from benchmark import scopes


def read(ctx):
    view = scopes.of(ctx)
    if view is None:
        return None
    return view.per_iter_ms(view.named_s("split_search"))
