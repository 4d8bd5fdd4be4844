"""Bytes on disk of the newest committed snapshot ÷ bytes of state."""


def read(ctx):
    if not ctx.disk_bytes:
        return None
    return ctx.disk_bytes / ctx.notes["state_bytes"]
