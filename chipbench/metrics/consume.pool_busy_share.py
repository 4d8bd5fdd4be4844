"""Share of the consume pool that worked: ``consume/*`` thread-seconds ÷ (``workers`` × ``restore/pipeline`` seconds)."""

from chipbench import span_reads


def read(ctx):
    work = span_reads.seconds(ctx, span_reads.under("consume/"))
    wall = span_reads.seconds(ctx, span_reads.named("restore/pipeline"))
    workers = span_reads.workers(ctx)
    return work / (workers * wall) if work is not None and workers and wall else None
