"""Caller-thread seconds after the pipelines (inflate, ``load_state_dict``, close-out), per restore: span ``restore/finalize``."""

from chipbench import span_reads


def read(ctx):
    return span_reads.seconds(ctx, span_reads.named("restore/finalize"))
