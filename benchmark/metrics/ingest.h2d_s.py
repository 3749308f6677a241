"""Sum of ``h2d_s`` over the training set's ``ingest_chunk`` events: one
thread copies, so these are its busy seconds of ``ingest.stream_s``."""
from benchmark import programs


def read(ctx):
    return programs.of(ctx).chunk_s("h2d_s")
