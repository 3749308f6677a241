"""Sum of ``commit_s`` over the training set's ``ingest_chunk`` events: one
thread folds chunks into the donated accumulator, so these are its busy
seconds of ``ingest.stream_s``."""
from benchmark import programs


def read(ctx):
    return programs.of(ctx).chunk_s("commit_s")
