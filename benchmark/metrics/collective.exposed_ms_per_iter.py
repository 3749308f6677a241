"""Device time under the scope ``hist_allreduce`` in the step, per iteration,
averaged over chips: what a chip spends starting and awaiting the cross-chip
reductions of the data-parallel grower (the root's histogram, one a level,
the exact leaf sums), the slowest shard's lag included, since a reduction
ends when the last chip has arrived. The union of the intervals of the ops
under the scope: where a reduction is split into a start and a done, what
runs between them is not counted. Nothing on one chip, where no reduction
is traced, and nothing from a program that has no such scope."""
from benchmark import scopes


def read(ctx):
    view = scopes.of(ctx)
    if view is None:
        return None
    return view.per_iter_ms(view.named_s("hist_allreduce"))
