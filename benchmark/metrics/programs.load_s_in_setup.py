"""Sum of ``duration_s`` over set-up's ``program_load`` events, all threads:
the builds and the cache reads (jax's ``backend_compile_duration``)."""
from benchmark import programs


def read(ctx):
    loads = programs.of(ctx).loads()
    return None if loads is None else sum(e["duration_s"] for e in loads)
