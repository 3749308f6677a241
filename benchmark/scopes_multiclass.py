"""What the readers of the K-class cell share: whether the cell trains K > 1
trees an iteration at all (its configuration says so: the readers stay
silent in every other cell, and read a program that emits no ``multiclass``
event, as the parent of PR 34 does not), the program's events, and kernel
launches counted under a scope."""
from benchmark import scopes


def num_class(ctx):
    """K of the cell's configuration; 0 where it trains one tree an
    iteration."""
    k = int(ctx.cell["cfg"]["params"].get("num_class", 1))
    return k if k > 1 else 0


def view_of(ctx):
    """The run's ScopeView in a K-class cell, else None."""
    return scopes.of(ctx) if num_class(ctx) else None


def last_event(ctx, kind):
    found = [e for e in ctx.obs_events if e.get("type") == kind]
    return found[-1] if found else None


def scope_ms_per_iter(ctx, *names):
    view = view_of(ctx)
    if view is None:
        return None
    return view.per_iter_ms(view.named_s(*names))


def launches_under(view, kernels, match):
    """Events of the Mosaic kernels ``kernels`` inside the step whose path
    holds a component ``match`` accepts, averaged over chips."""
    chips = len(view.c["chips"])
    n = sum(1 for k in range(chips)
            for name, _, _, parts in view._ops(k, (scopes.trace.STEP_MODULE,))
            if scopes.instruction(name) in kernels
            and any(match(p) for p in parts))
    return n / chips
