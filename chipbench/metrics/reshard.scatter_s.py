"""Thread-seconds of copying read shards into the assembly buffers, per restore: Σ durations of the ``reshard/scatter`` spans."""

from chipbench import span_reads


def read(ctx):
    return span_reads.seconds(ctx, span_reads.named("reshard/scatter")) or None
