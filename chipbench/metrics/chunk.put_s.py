"""Seconds in which an assembled leaf becomes a device array (one ``device_put`` of the whole array, then the template donated), per restore: Σ durations of the ``chunk/put`` spans."""

from chipbench import span_reads


def read(ctx):
    return span_reads.seconds(ctx, span_reads.named("chunk/put")) or None
