"""Mean device time under one ``class_tree`` scope (one class's grower call
inside the step: its root pass, level passes and leaf sums), in ms: the
scope's time an iteration over the K of the cell's configuration. Silent
from a program that has no such scope."""
from benchmark import scopes_multiclass as mc


def read(ctx):
    ms = mc.scope_ms_per_iter(ctx, "class_tree")
    return None if ms is None else ms / mc.num_class(ctx)
