"""Caller-thread seconds in ``sync_execute_read_reqs`` (pool, loop, pipelines, shutdown), per restore: span ``restore/pipeline``."""

from chipbench import span_reads


def read(ctx):
    return span_reads.seconds(ctx, span_reads.named("restore/pipeline"))
