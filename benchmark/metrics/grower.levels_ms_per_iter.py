"""Device time under every level group (``level_s<W>``, all widths), per
iteration."""
from benchmark import scopes


def read(ctx):
    view = scopes.of(ctx)
    if view is None:
        return None
    return view.per_iter_ms(view.level_s(lambda w: True))
