"""Bytes the device pack programs moved as 1- or 2-byte words ÷ bytes of state, per save: counters ``device_pack.bytes_w*``."""

from chipbench import width_reads


def read(ctx):
    return width_reads.narrow_share(ctx, width_reads.PACK)
