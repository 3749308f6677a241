"""Process start to the first device array ready, less the TPU runtime's start
(``harness.device_start_s``): imports, first program."""


def read(ctx):
    return ctx.spans.get("harness.import_init_s")
