"""Trees one iteration grows, as the program's ``multiclass`` event says when
the step is built (``trees_per_iter``: 7 for seven classes). Silent from a
program that emits no such event."""
from benchmark import scopes_multiclass as mc


def read(ctx):
    ev = mc.last_event(ctx, "multiclass") if mc.num_class(ctx) else None
    return ev["trees_per_iter"] if ev else None
