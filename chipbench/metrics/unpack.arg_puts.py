"""Host→device transfers of the ARGUMENTS of a restore's device programs (a slab's member offsets, a piece's box starts: not the slab or the piece), per restore: counter ``device_unpack.arg_puts``.  None for a program without the counter."""


def read(ctx):
    after = ctx.obs_after["counters"].get("device_unpack.arg_puts")
    n = ctx.count("restore")
    if after is None or not n:
        return None
    return (after - ctx.obs_before["counters"].get("device_unpack.arg_puts", 0)) / n
