"""Sum of ``encode_s`` over the training set's ``ingest_chunk`` events:
thread-seconds of the encoder threads, to set beside ``ingest.stream_s``."""
from benchmark import programs


def read(ctx):
    return programs.of(ctx).chunk_s("encode_s")
