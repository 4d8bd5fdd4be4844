"""Bytes that reached their place through the chunked path (a leaf over MAX_CHUNK_SIZE_BYTES, one consumer a dim-0 row range) ÷ bytes of state, per restore: counter ``chunked.read_bytes``."""

from chipbench import counter_reads


def read(ctx):
    return counter_reads.per_restore_state_byte(ctx, "chunked.read_bytes")
