"""True thread-seconds of consume work on the pool, per restore: Σ durations of the ``consume/*`` worker spans."""

from chipbench import span_reads


def read(ctx):
    return span_reads.seconds(ctx, span_reads.under("consume/"))
