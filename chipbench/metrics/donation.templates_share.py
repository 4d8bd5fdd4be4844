"""Templates donated ÷ array leaves restored: ``preparers/array.DONATION_STATS``."""


def read(ctx):
    n = ctx.count("restore")
    if not n or ctx.donated_templates is None:
        return None
    return ctx.donated_templates / (n * ctx.notes["array_leaves"])
