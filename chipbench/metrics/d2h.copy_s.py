"""Device → host copies on the staging pool (``np.asarray`` of a leaf or a packed slab), thread-seconds per save: Σ durations of the ``d2h/copy`` spans."""

from chipbench import span_reads


def read(ctx):
    return span_reads.save_seconds(ctx, span_reads.named("d2h/copy"))
