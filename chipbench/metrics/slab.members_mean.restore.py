"""Members a slab, mean over the window's device unpacks: attr ``members`` of the spans ``unpack/dispatch``."""

from chipbench import width_reads


def read(ctx):
    return width_reads.members_mean(ctx, "unpack/dispatch")
