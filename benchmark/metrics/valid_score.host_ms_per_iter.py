"""Host time inside the package's span ``valid_score`` (dispatching the
walk and the lookup, and whatever program it loads), per iteration."""
from benchmark import scopes


def read(ctx):
    view = scopes.of(ctx)
    if view is None:
        return None
    return view.per_iter_ms(view.host_span_s("valid_score"))
