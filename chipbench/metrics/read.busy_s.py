"""Thread-seconds of storage reads per restore: ``phase.read_s`` sum."""


def read(ctx):
    return ctx.hist_per("phase.read_s", "restore")
