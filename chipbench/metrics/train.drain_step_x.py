"""Mean step seconds while a save is in flight ÷ clean step seconds."""
from chipbench import arith


def read(ctx):
    slow = [r for r in ctx.timeline if r["op"] == "step" and r.get("in_flight")]
    clean = arith.clean_step_seconds(ctx.timeline)
    if not slow or not clean:
        return None
    return sum(r["t1"] - r["t0"] for r in slow) / len(slow) / clean
