"""Device time under the scope ``split_search`` over the K class trees of an
iteration (``split.device_ms_per_iter``'s reading; its list of cells is
closed), per iteration."""
from benchmark import scopes_multiclass as mc


def read(ctx):
    return mc.scope_ms_per_iter(ctx, "split_search")
