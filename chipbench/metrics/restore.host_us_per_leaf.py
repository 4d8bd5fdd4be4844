"""Caller-thread microseconds outside the pipeline a leaf: Σ spans ``restore/metadata`` + ``restore/plan`` + ``restore/finalize`` a restore ÷ array leaves."""

from chipbench import span_reads


def read(ctx):
    seconds = span_reads.seconds(
        ctx, span_reads.named("restore/metadata", "restore/plan", "restore/finalize")
    )
    return None if seconds is None else seconds / ctx.notes["array_leaves"] * 1e6
