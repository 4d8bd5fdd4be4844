"""Members a slab, mean over the window's packs: attr ``members`` of the spans ``pipeline/slab_pack``."""

from chipbench import width_reads


def read(ctx):
    return width_reads.members_mean(ctx, "pipeline/slab_pack")
