"""Plan seconds per take: the program's span ``take/plan``, mean."""


def read(ctx):
    plans = ctx.span_seconds("take/plan")
    return sum(plans) / len(plans) if plans else None
