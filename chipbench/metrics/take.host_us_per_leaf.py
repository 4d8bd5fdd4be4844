"""Caller-thread microseconds of plan a leaf: the program's span ``take/plan``, mean a save, ÷ array leaves."""


def read(ctx):
    plans = ctx.span_seconds("take/plan")
    if not plans:
        return None
    return sum(plans) / len(plans) / ctx.notes["array_leaves"] * 1e6
