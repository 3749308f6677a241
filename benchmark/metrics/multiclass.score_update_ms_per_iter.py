"""Device time under the scope ``score_update`` over the K class trees of an
iteration (each tree's leaf-value lookup and the add into its class's
scores), per iteration."""
from benchmark import scopes_multiclass as mc


def read(ctx):
    return mc.scope_ms_per_iter(ctx, "score_update")
