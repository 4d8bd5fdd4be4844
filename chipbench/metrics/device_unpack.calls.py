"""Device unpack programs run per restore: ``CALL_COUNTS["unpack"]``."""


def read(ctx):
    n = ctx.count("restore")
    return ctx.unpack_calls / n if n else None
