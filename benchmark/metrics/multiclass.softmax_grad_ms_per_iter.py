"""Device time under the scope ``grad`` (``front/grad`` in the step: the
softmax over the K scores of a row and the K gradient and hessian rows, once
an iteration), per iteration."""
from benchmark import scopes_multiclass as mc


def read(ctx):
    return mc.scope_ms_per_iter(ctx, "grad")
