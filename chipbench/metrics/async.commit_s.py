"""``async_take`` call → commit seen, mean seconds per save."""


def read(ctx):
    cycles = [r for r in ctx.timeline if r["op"] == "cycle"]
    return sum(r["t1"] - r["t0"] for r in cycles) / len(cycles) if cycles else None
