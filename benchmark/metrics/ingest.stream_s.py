"""``Dataset.construct_phases['stream_s']``: encode, upload, commit."""


def read(ctx):
    return ctx.construct_phases.get("stream_s")
