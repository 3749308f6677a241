"""Feature groups of the histogram kernel's grid, as the program's
``hist_path`` event says when the step is built (63 at 2,000 features x 64
padded bins; 1 where one accumulator block holds the whole width)."""


def read(ctx):
    paths = [e for e in ctx.obs_events if e.get("type") == "hist_path"]
    return paths[-1]["feature_groups"] if paths else None
