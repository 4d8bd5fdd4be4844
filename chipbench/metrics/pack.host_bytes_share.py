"""Bytes of the slabs packed on the host, by choice or by fallback, ÷ bytes of state, per save: counter ``slab.host_pack_bytes``."""

from chipbench import width_reads


def read(ctx):
    return width_reads.host_share(ctx, width_reads.PACK)
