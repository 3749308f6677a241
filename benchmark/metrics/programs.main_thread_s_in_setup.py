"""``trace_s + lower_s + duration_s`` of set-up's ``program_load`` events on
``MainThread``: what set-up's own path paid for programs, the prewarm and
ingest threads' share left out."""
from benchmark import programs


def read(ctx):
    loads = programs.of(ctx).loads(named=True)
    if loads is None:
        return None
    return sum(programs.load_seconds(e) for e in loads
               if e.get("thread") == programs.MAIN_THREAD)
