"""What prewarm failed to hide: the wait at the first dispatch for the
background compile (``aot_prewarm`` event, ``phase=adopted``)."""


def read(ctx):
    for e in ctx.obs_events:
        if e.get("type") == "aot_prewarm" and e.get("phase") == "adopted":
            return e.get("duration_s")
    return None
