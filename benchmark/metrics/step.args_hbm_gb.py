"""The compiled step's arguments on one device, in GB: ``argument_bytes`` of
the package's ``step_memory`` event."""
from benchmark import programs


def read(ctx):
    return programs.of(ctx).step_memory_gb("argument_bytes")
