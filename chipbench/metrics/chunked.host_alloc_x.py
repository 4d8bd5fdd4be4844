"""Bytes of the whole-array host buffers a restore makes to assemble its chunked leaves in ÷ bytes of state: counter ``chunked.host_assembly_bytes``."""

from chipbench import counter_reads


def read(ctx):
    return counter_reads.per_restore_state_byte(ctx, "chunked.host_assembly_bytes")
