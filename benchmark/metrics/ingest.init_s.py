"""``Dataset.construct_phases['init_s']`` (the package's own clock):
``Dataset.__init__``, which makes float64 vectors of label, weight and init
score."""


def read(ctx):
    return ctx.construct_phases.get("init_s")
