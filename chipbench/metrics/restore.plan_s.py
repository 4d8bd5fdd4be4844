"""Caller-thread seconds before the pipelines, per restore: spans ``restore/metadata`` + ``restore/plan``."""

from chipbench import span_reads


def read(ctx):
    return span_reads.seconds(ctx, span_reads.named("restore/metadata", "restore/plan"))
