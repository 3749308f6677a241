"""The package's span ``train_setup``: entry of ``lgb.train`` to the first
iteration (``span`` event)."""


def read(ctx):
    for e in ctx.obs_events:
        if e.get("type") == "span" and e.get("name") == "train_setup":
            return e.get("duration_s")
    return None
