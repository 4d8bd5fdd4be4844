"""Seconds in commit barriers per save: ``phase.barrier_s`` sum."""


def read(ctx):
    return ctx.hist_per("phase.barrier_s", "take")
