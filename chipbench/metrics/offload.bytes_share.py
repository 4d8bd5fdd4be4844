"""Bytes the eager offload moved device → pinned host ÷ bytes of state, per
save: ``host_offload.LAST_OFFLOAD_STATS`` read after each ``async_take``."""


def read(ctx):
    stats = ctx.notes["offload"][-ctx.count("cycle"):] if ctx.count("cycle") else []
    if not stats:
        return None
    moved = sum(s.get("device_offload_bytes", 0) for s in stats)
    return moved / (len(stats) * ctx.notes["state_bytes"])
