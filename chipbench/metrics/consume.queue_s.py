"""Task-seconds consume work waited for a pool worker, per restore: Σ ``queue_ns`` of the ``consume/*`` worker spans."""

from chipbench import span_reads


def read(ctx):
    return span_reads.attr(ctx, span_reads.under("consume/"), "queue_ns", 1e-9)
