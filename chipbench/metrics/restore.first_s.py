"""The process's first restore (template, restore, wait), timed in set-up."""


def read(ctx):
    first = [r for r in ctx.setup_timeline if r["op"] in ("template", "restore")]
    return first[-1]["t1"] - first[0]["t0"] if len(first) >= 2 else None
