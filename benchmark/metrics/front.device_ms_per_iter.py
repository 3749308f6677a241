"""Device time under the scope ``front`` (gradients, quantisation and the
root histogram: one kernel where they are fused, three passes ``grad`` /
``quant`` / ``hist0`` where they are not), per iteration."""
from benchmark import scopes


def read(ctx):
    view = scopes.of(ctx)
    if view is None:
        return None
    return view.per_iter_ms(view.named_s("front"))
