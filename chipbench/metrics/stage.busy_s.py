"""Thread-seconds of staging (D2H, digest) per save: ``phase.stage_s`` sum."""


def read(ctx):
    return ctx.hist_per("phase.stage_s", "take")
