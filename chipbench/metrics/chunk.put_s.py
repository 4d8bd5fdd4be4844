"""Seconds in which chunked leaves are put on the device, per restore: Σ durations of the ``chunk/put`` spans.  Where a leaf goes to the device chunk by chunk, one span a chunk, on a consume worker: the ``device_put`` call of the chunk from the read's mapping (the wait for it to land is ``chunk/land``, its join ``chunk/join``).  Where a leaf is assembled on the host (the CPU; a numpy, sharded or cast template), one span a leaf: the whole array's put, then the template donated."""

from chipbench import span_reads


def read(ctx):
    return span_reads.seconds(ctx, span_reads.named("chunk/put")) or None
