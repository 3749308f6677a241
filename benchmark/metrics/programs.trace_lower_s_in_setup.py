"""Sum of ``trace_s + lower_s`` over set-up's ``program_load`` events, all
threads: the tracing and lowering of the programs that were then loaded,
Python time that no cache saves."""
from benchmark import programs


def read(ctx):
    loads = programs.of(ctx).loads(named=True)
    if loads is None:
        return None
    return sum(e.get("trace_s", 0.0) + e.get("lower_s", 0.0) for e in loads)
