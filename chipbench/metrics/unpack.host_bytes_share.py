"""Bytes of the slabs unpacked on the host, by choice or by fallback, ÷ bytes of state, per restore: counter ``slab.host_unpack_bytes``."""

from chipbench import width_reads


def read(ctx):
    return width_reads.host_share(ctx, width_reads.UNPACK)
