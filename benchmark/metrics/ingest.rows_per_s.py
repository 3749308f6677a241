"""Training rows over the harness clock around ``Dataset.construct``."""


def read(ctx):
    s = ctx.spans.get("ingest.construct_s")
    return ctx.n_train / s if s else None
