"""Seconds in which a restore joins the chunks of its leaves on the device, per restore: Σ durations of the ``chunk/join`` spans (the worker that placed a leaf's last chunk runs the leaf's one compiled concatenate, waits for it, sets the leaf and donates its template; one join at a time a restore).  None where no chunked leaf went to the device chunk by chunk."""

from chipbench import span_reads


def read(ctx):
    return span_reads.seconds(ctx, span_reads.named("chunk/join")) or None
