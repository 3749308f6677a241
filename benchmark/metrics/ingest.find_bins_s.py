"""``Dataset.construct_phases['find_bins_s']`` (the package's own clock)."""


def read(ctx):
    return ctx.construct_phases.get("find_bins_s")
