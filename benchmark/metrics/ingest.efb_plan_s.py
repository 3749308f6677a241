"""``Dataset.construct_phases['efb_plan_s']`` (the package's own clock): the
bundling plan (the 50 k-row sample drawn, binned and searched for exclusive
features)."""


def read(ctx):
    return ctx.construct_phases.get("efb_plan_s")
