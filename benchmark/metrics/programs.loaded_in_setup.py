"""Executables built or read from the compile cache before the window opens:
the package's ``program_load`` events in set-up (``benchmark/programs.py``
says where set-up ends), every thread's."""
from benchmark import programs


def read(ctx):
    loads = programs.of(ctx).loads()
    return None if loads is None else len(loads)
