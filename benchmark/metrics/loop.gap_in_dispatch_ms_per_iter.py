"""Device-idle time while the innermost open span of the package is
``step_dispatch``, per iteration (``scopes.ScopeView.gap_split``)."""
from benchmark import scopes


def read(ctx):
    view = scopes.of(ctx)
    gaps = view.gap_split() if view is not None else None
    if not gaps or "step_dispatch" not in gaps:
        return None
    return view.per_iter_ms(gaps["step_dispatch"])
