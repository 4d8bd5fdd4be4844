"""Host side of ``jax.device_put`` on the restore path, thread-seconds per restore: Σ durations of the ``h2d/put`` spans."""

from chipbench import span_reads


def read(ctx):
    return span_reads.seconds(ctx, span_reads.named("h2d/put"))
