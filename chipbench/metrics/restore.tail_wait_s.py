"""Seconds the host waits in ``block_until_ready`` after ``Snapshot.restore`` returned, per restore: the timeline record end − the root span ``restore`` end."""

from chipbench import span_reads


def read(ctx):
    return span_reads.tail_wait(ctx)
