"""Seconds in which filled assembly buffers become device arrays (a ``device_put`` a device, then the array made of them), per restore: Σ durations of the ``reshard/assemble`` spans."""

from chipbench import span_reads


def read(ctx):
    return span_reads.seconds(ctx, span_reads.named("reshard/assemble")) or None
