"""Templates donated ÷ array leaves restored: ``preparers/array.DONATION_STATS``."""


def read(ctx):
    n = ctx.count("restore")
    return ctx.donated_templates / (n * ctx.notes["array_leaves"]) if n else None
