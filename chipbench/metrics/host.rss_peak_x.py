"""Host RSS peak over its value at window open ÷ bytes of state (polled)."""


def read(ctx):
    if not ctx.rss_peak_delta or ctx.rss_peak_delta <= 0:
        return None
    return ctx.rss_peak_delta / ctx.notes["state_bytes"]
