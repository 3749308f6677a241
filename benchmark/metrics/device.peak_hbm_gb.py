"""``memory_stats()['peak_bytes_in_use']`` after the window, in GB."""


def read(ctx):
    return ctx.memory_peak_bytes / 1e9
