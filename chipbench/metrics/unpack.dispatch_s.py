"""Dispatch of the per-member unpack programs of a slab, thread-seconds per restore: Σ durations of the ``unpack/dispatch`` spans."""

from chipbench import span_reads


def read(ctx):
    return span_reads.seconds(ctx, span_reads.named("unpack/dispatch"))
