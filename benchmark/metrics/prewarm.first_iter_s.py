"""``lgb.train`` entry to the end of the first iteration."""


def read(ctx):
    return ctx.spans.get("prewarm.first_iter_s")
