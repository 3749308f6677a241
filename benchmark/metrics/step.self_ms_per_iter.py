"""The step module's device time less what runs under ``front``,
``level_s*``, ``leaf_renew`` and ``score_update``: what no stage of the tree
owns, per iteration."""
from benchmark import scopes


def read(ctx):
    view = scopes.of(ctx)
    if view is None:
        return None
    return view.per_iter_ms(view.step_self_s())
