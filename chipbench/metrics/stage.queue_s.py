"""Task-seconds staging work waited for a pool worker, per save: Σ ``queue_ns`` of the ``stage/*`` worker spans."""

from chipbench import span_reads


def read(ctx):
    return span_reads.save_attr(ctx, span_reads.under("stage/"), "queue_ns", 1e-9)
