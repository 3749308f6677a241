"""``import jax``."""


def read(ctx):
    return ctx.spans.get("harness.import_jax_s")
