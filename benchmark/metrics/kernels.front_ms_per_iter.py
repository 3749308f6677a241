"""Device time of the fused gradient+quantise+root-histogram kernel
(``grad_quant_hist0``), per iteration."""
from benchmark import scopes


def read(ctx):
    view = scopes.of(ctx)
    if view is None:
        return None
    return view.per_iter_ms(view.kernel("grad_quant_hist0")[1])
