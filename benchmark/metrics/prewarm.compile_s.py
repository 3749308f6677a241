"""Duration of the package's ``compile`` event for ``fused_step_aot``."""


def read(ctx):
    for e in ctx.obs_events:
        if e.get("type") == "compile" and e.get("what") == "fused_step_aot":
            return e.get("duration_s")
    return None
