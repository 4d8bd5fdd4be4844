"""Bytes of host assembly buffers a restore makes ÷ bytes of state: the counter ``reshard.host_alloc_bytes`` over the window."""

from chipbench import counter_reads


def read(ctx):
    return counter_reads.per_restore_state_byte(ctx, "reshard.host_alloc_bytes")
