"""Device time under the scope ``route`` (the row router as a pass of its
own, between a level's split search and its grouped histogram kernel: XLA
gathers above 512 features, the Pallas route kernel below), per iteration.
Silent where routing is fused into the level kernel."""
from benchmark import scopes


def read(ctx):
    view = scopes.of(ctx)
    if view is None:
        return None
    return view.per_iter_ms(view.named_s("route"))
