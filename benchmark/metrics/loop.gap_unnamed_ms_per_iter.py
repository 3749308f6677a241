"""Device-idle time under no leaf span of the package (``train_iter``,
``boosting`` or ``eval`` themselves, or none), per iteration."""
from benchmark import scopes


def read(ctx):
    view = scopes.of(ctx)
    gaps = view.gap_split() if view is not None else None
    if not gaps or "step_dispatch" not in gaps:
        return None      # the program opens no such spans
    return view.per_iter_ms(gaps.get(scopes.UNNAMED, 0.0))
