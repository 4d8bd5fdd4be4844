"""Bytes the device unpack programs took as 1- or 2-byte words ÷ bytes of state, per restore: counters ``device_unpack.bytes_w*``."""

from chipbench import width_reads


def read(ctx):
    return width_reads.narrow_share(ctx, width_reads.UNPACK)
