"""Executables built or read from the cache inside the window (jax's
``backend_compile_duration`` events), per iteration."""


def read(ctx):
    return ctx.window.loads_in_window / ctx.window.window_iters
