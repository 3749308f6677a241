"""Device time under the scope ``bins_T`` in the step, per iteration: the
transpose of the shard's bin matrix that every tree makes where the step is
handed no cached copy (the row-sharded trainer opts out of the Dataset's
``bins_T``), which is what that opt-out costs a tree.

Where the data-parallel step says it is handed no cached copy and no op ran
under the scope, the reading is 0: the compiler took the transpose away. On
the v5e it does: the step takes its ``[N, 28]`` uint8 argument in a
feature-major layout (``{0,1}``), so ``bins.T`` is a bitcast. The step is
known to be the one that has the scope by the byte count its ``hist_path``
event carries, which came with it. Nothing where the step is fed the cached
copy, and nothing from a program that has no such scope."""
from benchmark import scopes


def read(ctx):
    view = scopes.of(ctx)
    if view is None:
        return None
    under = view.per_iter_ms(view.named_s("bins_T"))
    if under is not None:
        return under
    steps = [e for e in ctx.obs_events if e.get("type") == "hist_path"
             and "allreduce_bytes_per_iter" in e]
    return 0.0 if steps and not steps[-1]["bins_T_cached"] else None
