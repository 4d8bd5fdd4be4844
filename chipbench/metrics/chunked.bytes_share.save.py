"""Bytes staged as chunks (a leaf over MAX_CHUNK_SIZE_BYTES, one device slice and one copy a dim-0 row range) ÷ bytes of state, per save: counter ``chunked.write_bytes``.  None for a window with no take, and for a program that never raised the counter."""

COUNTER = "chunked.write_bytes"


def read(ctx):
    after, n = ctx.obs_after["counters"].get(COUNTER), ctx.count("take")
    if after is None or not n:
        return None
    return (after - ctx.obs_before["counters"].get(COUNTER, 0)) / n / ctx.notes["state_bytes"]
