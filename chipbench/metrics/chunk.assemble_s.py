"""Thread-seconds the consume workers spend copying read chunks into a leaf's host assembly buffer, per restore: Σ durations of the ``chunk/assemble`` spans."""

from chipbench import span_reads


def read(ctx):
    return span_reads.seconds(ctx, span_reads.named("chunk/assemble")) or None
