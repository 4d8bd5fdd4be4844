"""Caller-thread seconds in the overlap algebra and the assembly buffers' allocation of the sharded leaves, per restore: Σ durations of the ``reshard/plan`` spans."""

from chipbench import span_reads


def read(ctx):
    return span_reads.seconds(ctx, span_reads.named("reshard/plan")) or None
