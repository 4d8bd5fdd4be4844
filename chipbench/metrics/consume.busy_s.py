"""Thread-seconds of consuming (H2D, unpack) per restore: ``phase.consume_s`` sum."""


def read(ctx):
    return ctx.hist_per("phase.consume_s", "restore")
