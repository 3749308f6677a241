"""Megabytes one iteration hands to the cross-chip reduction, as the
program's ``hist_path`` event counts them from shapes when the data-parallel
step is built (``allreduce_bytes_per_iter``: the root's ``[3, F, B]``
float32 histogram, ``[S, 3, F, B]`` for each level of a balanced tree that
builds one (not the route-only last), the ``[3, L]`` leaf sums; 6.88 MB at
28 features x 64 bins x 255 leaves). A count, not a time. Nothing from a
step that is not data-parallel."""


def read(ctx):
    sent = [e["allreduce_bytes_per_iter"] for e in ctx.obs_events
            if e.get("type") == "hist_path"
            and "allreduce_bytes_per_iter" in e]
    return sent[-1] / 1e6 if sent else None
