"""Bytes a restore reads from the sink ÷ bytes of state (1.0: every byte read once): the counter ``bytes_read`` over the window."""

from chipbench import counter_reads


def read(ctx):
    return counter_reads.per_restore_state_byte(ctx, "bytes_read")
