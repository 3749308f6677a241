"""Set-up's ``program_load`` events with ``cache == "miss"``: programs XLA
built in this run and wrote to the persistent cache. 0 says the line's
set-up numbers are warm ones; nothing where the events carry no ``cache``."""
from benchmark import programs


def read(ctx):
    loads = programs.of(ctx).loads(named=True)
    if loads is None:
        return None
    return sum(1 for e in loads if e.get("cache") == "miss")
