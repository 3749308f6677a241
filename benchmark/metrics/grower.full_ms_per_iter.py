"""Device time under the widest level group (``level_s<W>``, W =
num_leaves // 2: the deepest levels and the unbalanced tail), per
iteration."""
from benchmark import scopes


def read(ctx):
    view = scopes.of(ctx)
    if view is None:
        return None
    full = int(ctx.cell["cfg"]["params"]["num_leaves"]) // 2
    return view.per_iter_ms(view.level_s(lambda w: w == full))
