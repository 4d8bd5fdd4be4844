"""Thread-seconds the staging workers spend in the device slice of a chunk and the wait for it, per save: Σ durations of the ``chunk/slice`` spans."""

from chipbench import span_reads


def read(ctx):
    return span_reads.save_seconds(ctx, span_reads.named("chunk/slice")) or None
