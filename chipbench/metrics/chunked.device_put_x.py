"""Bytes put on the device chunk by chunk, straight from the read, with no whole-array host buffer ÷ bytes of state, per restore: counter ``chunked.device_put_bytes``.  The device path's engagement: the chunked share of the state where every chunked leaf takes it, 0 where all of them are assembled on the host."""

from chipbench import counter_reads


def read(ctx):
    return counter_reads.per_restore_state_byte(ctx, "chunked.device_put_bytes")
