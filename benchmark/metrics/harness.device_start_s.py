"""``jax.devices()``: the TPU runtime's own start. The one part of a run's wall
set-up that ``setup_s`` leaves out (PERF.md section 2)."""


def read(ctx):
    return ctx.spans.get("harness.device_start_s")
