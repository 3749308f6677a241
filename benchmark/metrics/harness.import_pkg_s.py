"""``import lightgbm_tpu`` (places the compile cache, loads the native encoder)."""


def read(ctx):
    return ctx.spans.get("harness.import_pkg_s")
