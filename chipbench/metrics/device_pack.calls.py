"""Device pack programs run per save: ``ops/device_pack.CALL_COUNTS["pack"]``."""


def read(ctx):
    saves = ctx.count("take")
    return ctx.pack_calls / saves if saves else None
