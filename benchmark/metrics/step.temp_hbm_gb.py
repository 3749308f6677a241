"""The compiled step's temporaries on one device, in GB: ``temp_bytes`` of
the package's ``step_memory`` event (``Compiled.memory_analysis()``), which
``device.peak_hbm_gb`` does not hold."""
from benchmark import programs


def read(ctx):
    return programs.of(ctx).step_memory_gb("temp_bytes")
