"""Checksums of the staged bytes, thread-seconds per save: Σ durations of the ``stage/digest`` worker spans."""

from chipbench import span_reads


def read(ctx):
    return span_reads.save_seconds(ctx, span_reads.named("stage/digest"))
