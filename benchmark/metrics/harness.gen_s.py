"""Seconds the harness took to make the rows from the seed."""


def read(ctx):
    return ctx.spans.get("harness.gen_s")
