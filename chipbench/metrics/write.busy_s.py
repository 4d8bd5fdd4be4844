"""Thread-seconds of storage writes per save: ``phase.write_s`` sum."""


def read(ctx):
    return ctx.hist_per("phase.write_s", "take")
