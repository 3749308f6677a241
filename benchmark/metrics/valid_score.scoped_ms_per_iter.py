"""Device time under the scope ``valid_score``, whatever module or kernel
does the work, per iteration.

The one to keep of two: the accepted ``valid_score.device_ms_per_iter`` sums
the same time by module names (``jit_route_bins*``, ``jit_wrapped*``) and
reads the same to 0.002% (PERF.md section 5). Once two PRs' ledger lines
show them agreeing, a ``benchmark`` issue retires that one."""
from benchmark import scopes


def read(ctx):
    view = scopes.of(ctx)
    if view is None:
        return None
    return view.per_iter_ms(view.named_s("valid_score",
                                         module_prefixes=None))
