"""``trace_s + lower_s + duration_s`` of the ``program_load`` events with
``iteration == 1``: the part of ``prewarm.first_iter_s`` that is neither the
barrier nor the step."""
from benchmark import programs


def read(ctx):
    loads = programs.of(ctx).loads(named=True)
    if loads is None:
        return None
    return sum(programs.load_seconds(e) for e in loads
               if e.get("iteration") == 1)
