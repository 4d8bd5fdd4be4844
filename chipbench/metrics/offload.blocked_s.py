"""Wall seconds of the ``async_take`` call per save: the train loop stands."""


def read(ctx):
    calls = [r for r in ctx.timeline if r["op"] == "take" and r.get("asynchronous")]
    return sum(r["t1"] - r["t0"] for r in calls) / len(calls) if calls else None
